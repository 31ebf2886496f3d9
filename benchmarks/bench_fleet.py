"""Fleet-scale multi-tenant streaming: SLO tiers, admission, degradation.

Two sections:

1. **Real streams (correctness anchor)** — a few co-keyed threshold-0
   streams run through the actual service/fleet data path: results must be
   bit-identical to per-frame ``detect``.

2. **Fleet simulation (~1k streams)** — the *control plane* is real (the
   actual :class:`FleetScheduler`: admission against the calibrated
   capacity budget, the tier-ordered degradation ladder, per-tier governor
   placements and the modeled-energy ledger); the *data plane* is modeled
   (per-session recompute fractions follow a scenario duty-cycle model that
   responds to the degraded config, exactly the quantity the fleet's
   demand predictor consumes via each session's work_frac EMA).  Load
   points sweep nominal (1x), overload (2x: the ladder absorbs it with
   zero dropped frames), and extreme (6x duty surge while one big pod is
   thermally throttled to half rate: the ladder exhausts and best-effort
   frames are shed, counted).  Reported per load point:
   per-tier latency percentiles, aggregate delivered windows/s vs a
   no-tier single-flush baseline, admission/degradation/drop counts, and
   modeled J/detection.
"""

from __future__ import annotations

import numpy as np

from .common import print_table, save_rows

DT = 0.05                     # flush-tick length (s): the serving cadence
TIER_SLO_MS = {"realtime": 50.0, "standard": 120.0, "best_effort": 400.0}
TIER_FPS = {"realtime": 15.0, "standard": 10.0, "best_effort": 5.0}
# scenario duty cycle: fraction of each frame's windows that change
# (repro.stream.synthetic scenarios, roughly ordered by activity)
SCENARIO_DUTY = {"static_cctv": 0.05, "intermittent_cctv": 0.2,
                 "moving_face": 0.5, "lighting_drift": 0.7,
                 "camera_pan": 0.9}
# (load multiplier, per-pod throttle): nominal, 2x overload the ladder
# absorbs, and an extreme point — 6x duty surge while big1 is thermally
# throttled to half rate — that exhausts the ladder and forces shedding
POINTS = ((1.0, None), (2.0, None), (6.0, (1.0, 0.5, 1.0)))

SIM_COLS = ["load", "tier", "latency_ms_p50", "latency_ms_p95",
            "latency_ms_p99", "slo_ms", "slo_met"]
SUM_COLS = ["load", "throttled", "windows_per_s",
            "baseline_windows_per_s", "admitted", "rejected",
            "degrade_events", "restore_events", "ladder_levels",
            "frames_dropped", "J_per_detection", "baseline_J_per_detection",
            "demand_over_capacity"]


def _session_specs(n: int, seed: int = 0) -> list[dict]:
    """Deterministic tenant mix: tiers x scenarios x shape buckets."""
    rng = np.random.default_rng(seed)
    tiers = ["realtime", "standard", "best_effort"]
    scen = list(SCENARIO_DUTY)
    shapes = [(64, 64), (96, 96)]
    return [{"tier": tiers[i % 3],
             "scenario": scen[int(rng.integers(len(scen)))],
             "shape": shapes[i % 2],
             "fps": TIER_FPS[tiers[i % 3]]}
            for i in range(n)]


def _model_frac(duty: float, load: float, config) -> float:
    """Modeled recompute fraction of one session under ``config``: the
    keyframe share (1/interval full refreshes) plus the duty-cycle share,
    damped by the raised change threshold (each threshold step of the
    ladder suppresses ~30% of the remaining changed tiles)."""
    kf = 1.0 / config.keyframe_interval if config.keyframe_interval else 0.0
    thr_damp = 0.9 ** round(config.threshold / 0.01) \
        if config.threshold else 1.0
    return float(min(1.0, kf + min(1.0, duty * load) * thr_damp))


def _percentiles(ms: list[float]) -> tuple[float, float, float]:
    a = np.asarray(ms) * 1e3
    return (float(np.percentile(a, 50)), float(np.percentile(a, 95)),
            float(np.percentile(a, 99)))


# --------------------------------------------------------- simulation
def run_sim(n_streams: int, ticks: int, fast: bool) -> list[dict]:
    from repro.core import Detector, EngineConfig, paper_shaped_cascade
    from repro.scheduling.dvfs import binding_slo, select_operating_points
    from repro.scheduling.energy import EnergyAccount, pod_operating_points
    from repro.serve import (DetectorService, FleetConfig, FleetScheduler,
                             PodSpec, ServiceConfig)
    from repro.stream import StreamConfig

    det = Detector(paper_shaped_cascade(0, stage_sizes=[3, 4, 5, 6, 8]),
                   EngineConfig(mode="wave", pad_multiple=32, step=2,
                                scale_factor=1.3, min_neighbors=2))
    pods = (PodSpec("big0", 1.0, "big"), PodSpec("big1", 1.0, "big"),
            PodSpec("little0", 0.45, "LITTLE"))
    scfg = StreamConfig(tile=12, threshold=0.0, keyframe_interval=10,
                        degrade_keyframe_mult=2.0,
                        degrade_threshold_add=0.01, max_degrade_level=3)
    specs = _session_specs(n_streams)

    # size the fleet so the nominal (1x) mix sits at ~70% of capacity: the
    # capacity model is exactly what admission/degradation budget against
    probe = DetectorService(det, ServiceConfig(pods=pods))
    units_by_shape = {s: probe._work_units(s)
                      for s in {sp["shape"] for sp in specs}}
    windows_by_shape = {
        (h, w): det.batch_plan(*det._bucket_hw(h, w)).n_windows_total
        for (h, w) in units_by_shape}
    demand0 = sum(units_by_shape[sp["shape"]] * sp["fps"]
                  * _model_frac(SCENARIO_DUTY[sp["scenario"]], 1.0, scfg)
                  for sp in specs)
    capacity = demand0 / 0.70
    shares = np.asarray([1.0, 1.0, 0.45])
    pod_rates = capacity * shares / shares.sum()
    ladders = tuple(pod_operating_points(p.cluster) for p in pods)
    mean_frac = demand0 / sum(units_by_shape[sp["shape"]] * sp["fps"]
                              for sp in specs)

    rows: list[dict] = []
    for load, throttle in (POINTS[:2] if fast else POINTS):
        svc = DetectorService(det, ServiceConfig(
            pods=pods, stream_config=scfg,
            tier_slos=TIER_SLO_MS))
        svc.seed_rates(pod_rates)
        fleet = FleetScheduler(svc, FleetConfig(
            admission_prior=min(1.0, 1.25 * mean_frac)))
        admitted = []
        for sp in specs:
            fs = fleet.admit(sp["shape"], sp["fps"], tier=sp["tier"],
                             stream_config=scfg)
            if fs is not None:
                fs.duty = SCENARIO_DUTY[sp["scenario"]]
                fs.windows = windows_by_shape[sp["shape"]]
                admitted.append(fs)

        # capacity events (pod throttling) strike AFTER admission — the
        # fleet re-budgets against the reduced rate sum
        run_rates = pod_rates * np.asarray(throttle if throttle
                                           else (1.0,) * len(pods))
        capacity_run = float(run_rates.sum())
        fleet.capacity_units_per_s = capacity_run

        acct = EnergyAccount(len(pods))
        base_acct = EnergyAccount(len(pods))
        lat: dict[str, list[float]] = {t: [] for t in TIER_SLO_MS}
        base_lat: list[float] = []
        backlog = base_backlog = 0.0
        windows = base_windows = 0.0
        frames = base_frames = 0.0
        dropped_frames = 0.0
        for _tick in range(ticks):
            for fs in admitted:
                fs.note_work_frac(_model_frac(fs.duty, load,
                                              fs.session.video.config))
            fleet.rebalance()
            by_tier: dict[str, list] = {t: [] for t in TIER_SLO_MS}
            for fs in admitted:
                by_tier[fs.tier].append(fs)
            demand = {t: sum(fs.demand_units_per_s() for fs in ss)
                      for t, ss in by_tier.items()}
            exhausted = all(
                fs.degrade_level >= fs.base_config.max_degrade_level
                for fs in admitted if fs.tier != "realtime")
            # shed (fleet semantics): only best_effort, only once the
            # ladder is spent, only the units that exceed raw capacity
            shed_u = 0.0
            total = sum(demand.values())
            if exhausted and total > capacity_run:
                # shed to 95% of capacity, not 100%: the recovered headroom
                # is what drains the backlog the transient built up
                shed_u = min(demand["best_effort"],
                             total - 0.95 * capacity_run)
                be_frames = sum(fs.fps for fs in by_tier["best_effort"])
                if demand["best_effort"] > 0:
                    shed_frac = shed_u / demand["best_effort"]
                    dropped_frames += shed_frac * be_frames * DT
                    windows -= shed_frac * DT * sum(
                        fs.windows * fs.fps for fs in by_tier["best_effort"])
            # tier-ordered flushes, each planned against ITS deadline —
            # bounded by its sustainable share of the tick (the governor
            # would otherwise stretch every flush to its full SLO and the
            # backlog would grow without bound at any utilization)
            total_u = max(total - shed_u, 1e-9) * DT
            t_cursor = 0.0
            for tier in ("realtime", "standard", "best_effort"):
                u = demand[tier] * DT
                if tier == "best_effort":
                    u = max(u - shed_u * DT, 0.0)
                if u <= 0:
                    continue
                slo = max(min(TIER_SLO_MS[tier] / 1e3 - t_cursor,
                              DT * u / total_u), 1e-3)
                d = select_operating_points(u, run_rates, ladders, slo,
                                            wake_J=0.02)
                busy = [u_i / r if r > 0 else 0.0 for u_i, r in
                        zip(np.asarray(d.rates) / sum(d.rates) * u, d.rates)]
                acct.charge_shard(d.ops, busy, [0.0] * len(pods),
                                  slo_s=TIER_SLO_MS[tier] / 1e3,
                                  wake_J=0.02,
                                  tier_slos={tier: TIER_SLO_MS[tier] / 1e3})
                t_cursor += d.makespan
                lat[tier].append(backlog + t_cursor)
            backlog = max(backlog + t_cursor - DT, 0.0)
            frames += sum(fs.fps for fs in admitted) * DT
            windows += sum(fs.windows * fs.fps for fs in admitted) * DT

            # no-tier baseline: one flush, binding SLO, no degradation
            bu = sum(fs.base_units * fs.fps * _model_frac(fs.duty, load,
                                                          scfg)
                     for fs in admitted) * DT
            bd = select_operating_points(
                bu, run_rates, ladders,
                min(binding_slo([s / 1e3 for s in TIER_SLO_MS.values()]),
                    DT),
                wake_J=0.02)
            bbusy = [u_i / r if r > 0 else 0.0 for u_i, r in
                     zip(np.asarray(bd.rates) / sum(bd.rates) * bu,
                         bd.rates)]
            base_acct.charge_shard(bd.ops, bbusy, [0.0] * len(pods),
                                   slo_s=bd.makespan, wake_J=0.02)
            base_lat.append(base_backlog + bd.makespan)
            base_backlog = max(base_backlog + bd.makespan - DT, 0.0)
            # queueing starves baseline throughput once demand > capacity
            served = min(1.0, DT / bd.makespan) if bd.makespan > 0 else 1.0
            base_frames += sum(fs.fps for fs in admitted) * DT * served
            base_windows += sum(fs.windows * fs.fps
                                for fs in admitted) * DT * served

        fstats = svc.stats().fleet
        sim_s = ticks * DT
        for tier in ("realtime", "standard", "best_effort"):
            if not lat[tier]:
                continue
            p50, p95, p99 = _percentiles(lat[tier])
            rows.append({"mode": "sim", "load": load, "tier": tier,
                         "latency_ms_p50": p50, "latency_ms_p95": p95,
                         "latency_ms_p99": p99,
                         "slo_ms": TIER_SLO_MS[tier],
                         "slo_met": bool(p95 <= TIER_SLO_MS[tier])})
        bp50, bp95, bp99 = _percentiles(base_lat)
        rows.append({"mode": "sim_baseline", "load": load, "tier": "(all)",
                     "latency_ms_p50": bp50, "latency_ms_p95": bp95,
                     "latency_ms_p99": bp99,
                     "slo_ms": min(TIER_SLO_MS.values()),
                     "slo_met": bool(bp95 <= min(TIER_SLO_MS.values()))})
        rows.append({
            "mode": "sim_summary", "load": load,
            "windows_per_s": (windows - 0.0) / sim_s,
            "baseline_windows_per_s": base_windows / sim_s,
            "admitted": fstats.admitted, "rejected": fstats.rejected,
            "degrade_events": fstats.degrade_events,
            "restore_events": fstats.restore_events,
            "ladder_levels": sorted({fs.degrade_level for fs in admitted}),
            "ladder_exhausted": bool(exhausted),
            "frames_dropped": dropped_frames,
            "frames_delivered": frames - dropped_frames,
            "J_per_detection": acct.total_J / max(frames - dropped_frames,
                                                  1.0),
            "baseline_J_per_detection": base_acct.total_J
            / max(base_frames, 1.0),
            "slo_met_by_tier": acct.slo_met_by_tier(),
            "throttled": throttle is not None,
            "demand_over_capacity": fleet.demand_units_per_s()
            / capacity_run,
            "capacity_units_per_s": capacity_run,
        })
    return rows


# -------------------------------------------------------- real streams
def run_real(fast: bool) -> list[dict]:
    from repro.core import Detector, EngineConfig, paper_shaped_cascade
    from repro.serve import (DetectorService, FleetConfig, FleetScheduler,
                             ServiceConfig)
    from repro.stream import StreamConfig, make_video

    det = Detector(paper_shaped_cascade(0, stage_sizes=[3, 4, 5, 6, 8]),
                   EngineConfig(mode="wave", pad_multiple=32, step=2,
                                scale_factor=1.3, min_neighbors=2))
    scfg = StreamConfig(tile=12, threshold=0.0, keyframe_interval=4,
                        degrade_keyframe_mult=2.0, max_degrade_level=3)
    svc = DetectorService(det, ServiceConfig(stream_config=scfg,
                                             tier_slos=TIER_SLO_MS))
    units = svc._work_units((96, 96))
    svc.seed_rates([100.0 * units])
    fleet = FleetScheduler(svc, FleetConfig())
    n_frames = 3 if fast else 6
    vids = [make_video("static_cctv", n_frames=n_frames, h=96, w=96, seed=s)
            for s in (0, 1)]
    sessions = [fleet.admit((96, 96), fps=10.0, tier=t)
                for t in ("realtime", "best_effort")]
    parity = True
    for t in range(n_frames):
        reqs = [fs.submit_frame(v[t][0])
                for fs, v in zip(sessions, vids)]
        fleet.flush()
        for r, v in zip(reqs, vids):
            parity &= bool(np.array_equal(r.result(timeout=120),
                                          det.detect(v[t][0])))
    st = svc.stats()
    return [{"mode": "real", "streams": len(sessions),
             "frames": st.stream.frames_done,
             "threshold0_parity": parity,
             "frame_modes": st.stream.frame_modes,
             "window_skip_frac": st.stream.window_skip_frac,
             "plan_groups": st.fleet.plan_groups}]


def main(fast: bool = False):
    n_streams = 150 if fast else 1000
    ticks = 60 if fast else 200
    rows = run_real(fast)
    print_table(rows, cols=["mode", "streams", "frames",
                            "threshold0_parity", "window_skip_frac",
                            "plan_groups"])
    sim = run_sim(n_streams, ticks, fast)
    print()
    print_table([r for r in sim if r["mode"] in ("sim", "sim_baseline")],
                cols=SIM_COLS)
    print()
    print_table([r for r in sim if r["mode"] == "sim_summary"],
                cols=SUM_COLS)
    rows += sim
    save_rows("bench_fleet", rows)
    return rows


if __name__ == "__main__":
    main()
