# The cascade plan layer: one typed model of the cascade workload
# (pyramid geometry, segment/capacity ladders, slot/SAT layout, packed-tail
# backend choices), compiled once per (bucket, batch) and consumed by thin
# executors in repro.core.engine; the device stream step's geometry is
# compiled once per stream plan for repro.stream.engine.
from .ir import (CascadePlan, LevelPlan, LevelWavePlan,  # noqa: F401
                 SegmentPlan, SlotLayout, StreamStatePlan)
from .compiler import (CAP_FLOOR, BATCH_CAP_FLOOR,  # noqa: F401
                       STREAM_DECODE_CAP,
                       compile_level_plan, compile_plan,
                       compile_stream_plan,
                       level_capacities, n_compactions, plan_cache_info,
                       segment_spans, segment_work_units, select_backend,
                       select_head_mode, shared_capacities,
                       validate_config, window_limits)
from .geometry import StreamGeometry  # noqa: F401
