"""Chip benchmark of the served Haar-cascade face detector.

``python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU it is started on.  Everything
that measures lives here and imports nothing of ``src/`` but the system under
test: the configurations (``configs/``), the traffic mixes (``traffic/``), the
per-layer metric readers (``metrics/``), the scene generators, the plain
reference, the operation and byte counts, the peak table and the reduction of
profiler traces.
"""
