#!/usr/bin/env bash
# Tier-1 gate + a reference-mode benchmark smoke, as run by CI.
#   ./scripts/ci.sh          full tier-1 + bench smoke
#   FAST=1 ./scripts/ci.sh   same suite, fast benchmark settings only
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "=== tier-1: pytest ==="
python -m pytest -x -q

echo "=== analysis: repro.analysis (trace-safety, plan-IR contracts, kernel oracles) ==="
python -m repro.analysis src tests benchmarks scripts examples --json ANALYSIS.json
python - <<'EOF'
# the gate must stay fast enough to run on every push (budget: < 5s)
import json
secs = json.load(open("ANALYSIS.json"))["seconds"]
assert secs < 5.0, f"repro.analysis took {secs}s (budget 5s) — profile it"
print(f"repro.analysis budget OK: {secs}s < 5s")
EOF

echo "=== smoke: packed-tail crossover (pallas == gather oracle, bit-exact) ==="
python scripts/crossover_smoke.py

echo "=== smoke: plan layer (ladder-chosen backends bit-exact, stats reflect plan) ==="
python scripts/plan_smoke.py

echo "=== smoke: bench_kernels (fused head vs split, bit-exact + crossover) ==="
python -m benchmarks.run --fast --only bench_kernels --artifacts .
python - <<'EOF'
# The fused Haar-head megakernel must be bit-exact against the split
# three-dispatch path at every pyramid level of the dense workload, and
# wherever the autotuner's crossover chose "fused" the fused timing must
# actually be at least as fast as split (1.25x timing-noise tolerance).
import json

rows = json.load(open("BENCH_kernels.json"))["rows"]
fused = [r for r in rows if r.get("kernel") == "fused_head"]
assert fused, "no fused_head rows in BENCH_kernels.json"
assert all(r["bit_exact"] for r in fused), \
    "fused head not bit-exact vs the split path"
chosen = [r for r in fused if r["mode"] == "fused"]
for r in chosen:
    assert r["fused_ms"] <= r["split_ms"] * 1.25, \
        f"tuner chose fused at {r['shape']} but fused is slower " \
        f"({r['fused_ms']:.2f}ms vs {r['split_ms']:.2f}ms)"
tuned = next(r for r in rows if r.get("kernel") == "fused_head_autotune")
print(f"fused head OK: bit-exact at {len(fused)} level(s), fused wins "
      f"{len(chosen)}/{len(fused)}, tile={tuned['shape']}, "
      f"crossover={tuned['crossover']}")
EOF

echo "=== smoke: bench_detector (batched head/tail split + crossover, fast) ==="
python -m benchmarks.run --fast --only bench_detector --artifacts .

echo "=== smoke: bench_rit (content/RIT relation, fast) ==="
python -m benchmarks.run --fast --only bench_rit

echo "=== smoke: bench_video (tile-reuse + level skip, fast) ==="
python -m benchmarks.run --fast --only bench_video --artifacts .
python - <<'EOF'
# High-motion streams must track per-frame detect within 5%: for each
# adversarial scenario the stream must reach 0.95x (3% timing-noise
# tolerance on the ratio, in line with the other benchmark gates).
# Streaming must keep threshold-0 bit-identity with zero warmed rebuilds.
import json

rows = json.load(open("BENCH_video.json"))["rows"]
by = {r["scenario"]: r for r in rows}
for kind in ("moving_face", "camera_pan"):
    r = by[kind]
    assert r["speedup"] >= 0.95 * 0.97, \
        f"{kind}: streaming fell to {r['speedup']:.3f}x of per-frame detect"
for r in rows:
    if r["threshold"] <= 0:
        assert r["exact"] is True, f"{r['scenario']} lost bit-identity"
    assert r["rebuilds"] == 0, f"{r['scenario']} rebuilt programs warm"
print(f"video stream OK: static {by['static_cctv']['stream_fps']:.0f} fps, "
      f"high-motion "
      + ", ".join(f"{k} {by[k]['speedup']:.2f}x"
                  for k in ("moving_face", "camera_pan")))
EOF

echo "=== smoke: bench_energy (DES energy + serving governor Pareto, fast) ==="
python -m benchmarks.run --fast --only bench_energy --artifacts .
python - <<'EOF'
# The governor must meet the SLO at least as often as either static
# extreme at every point of BENCH_energy.json's Pareto front, and at some
# SLO beat both extremes on modeled Joules/detection (5% model-drift tol).
import json

rows = json.load(open("BENCH_energy.json"))["rows"]
serving = [r for r in rows if r.get("mode") == "serving"]
by_slo = {}
for r in serving:
    by_slo.setdefault(round(r["slo_ms"], 3), {})[r["policy"]] = r
assert by_slo, "no serving rows in BENCH_energy.json"
wins = 0
for slo, pol in sorted(by_slo.items()):
    gov, mx, lt = pol["energy"], pol["max"], pol["little"]
    assert gov["slo_met_frac"] >= max(mx["slo_met_frac"],
                                      lt["slo_met_frac"]) - 1e-9, \
        f"governor misses SLO more than an extreme at slo={slo}ms"
    for ext in (mx, lt):
        if ext["slo_met_frac"] >= gov["slo_met_frac"] - 1e-9:
            assert gov["J_per_detection"] <= \
                ext["J_per_detection"] * 1.05, \
                f"governor beaten by {ext['policy']} at slo={slo}ms"
    # Pareto-dominance at this SLO: against each extreme the governor
    # either buys strictly better SLO attainment, or matches/beats its
    # energy (2% model-drift tolerance)
    if all(gov["slo_met_frac"] > ext["slo_met_frac"] + 1e-9
           or gov["J_per_detection"] <= 1.02 * ext["J_per_detection"]
           for ext in (mx, lt)):
        wins += 1
assert wins >= 1, "governor never Pareto-dominates both static extremes"
print(f"governor Pareto OK: dominates-or-ties both extremes at "
      f"{wins}/{len(by_slo)} SLO points")
EOF

echo "=== smoke: bench_fleet (multi-tenant tiers + admission + ladder, fast) ==="
python -m benchmarks.run --fast --only bench_fleet --artifacts .
python - <<'EOF'
# Tier contract from BENCH_fleet.json: under overload (2x) the realtime
# tier's p95 must stay at or under best-effort's, realtime must meet its
# SLO, no frame may be dropped before the degradation ladder is exhausted,
# and tiered serving must not cost aggregate throughput vs the no-tier
# single-flush baseline (2% model tolerance).
import json

rows = json.load(open("BENCH_fleet.json"))["rows"]
over = max(r["load"] for r in rows if r.get("mode") == "sim_summary")
assert over >= 2.0, f"no overload point in BENCH_fleet.json (max {over}x)"
tiers = {r["tier"]: r for r in rows
         if r.get("mode") == "sim" and r["load"] == over}
assert tiers["realtime"]["latency_ms_p95"] <= \
    tiers["best_effort"]["latency_ms_p95"] + 1e-9, \
    "realtime p95 exceeds best_effort p95 under overload"
assert tiers["realtime"]["slo_met"], "realtime misses its SLO under overload"
summ = next(r for r in rows
            if r.get("mode") == "sim_summary" and r["load"] == over)
if max(summ["ladder_levels"]) < 3:    # ladder not exhausted -> zero drops
    assert summ["frames_dropped"] == 0, \
        "frames dropped before the degradation ladder was exhausted"
assert summ["windows_per_s"] >= 0.98 * summ["baseline_windows_per_s"], \
    "tiered fleet throughput fell below the no-tier baseline"
print(f"fleet tier contract OK at {over}x: rt p95 "
      f"{tiers['realtime']['latency_ms_p95']:.1f}ms <= be p95 "
      f"{tiers['best_effort']['latency_ms_p95']:.1f}ms, "
      f"dropped={summ['frames_dropped']:.0f}, "
      f"degrade_events={summ['degrade_events']}")
EOF

echo "CI OK"
