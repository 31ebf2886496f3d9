"""Streaming video detection demo: temporal tile-reuse over a CCTV-style
synthetic stream, one device-resident step per frame, plus concurrent
stream sessions through the serving front-end.

    PYTHONPATH=src python examples/video_stream.py
"""

import numpy as np

from repro.core import Detector, EngineConfig
from repro.configs.viola_jones import pretrained
from repro.serve import DetectorService, PodSpec, ServiceConfig
from repro.stream import StreamConfig, VideoDetector, make_video
from repro.compile_cache import use_compile_cache


def main() -> None:
    casc, _ = pretrained()
    det = Detector(casc, EngineConfig(mode="wave", step=2,
                                      scale_factor=1.25, min_neighbors=2))
    video = make_video("static_cctv", n_frames=10, h=160, w=160, seed=7)
    det = det.calibrated(video[0][0])

    print("== single stream (threshold 0: bit-identical to per-frame) ==")
    vd = VideoDetector(det, StreamConfig(tile=20, threshold=0.0,
                                         keyframe_interval=8))
    for frame, _gt in video:
        rects, st = vd.process(frame)
        assert np.array_equal(rects, det.detect(frame))
        print(f"frame {st.frame_idx:2d} {st.mode:11s} "
              f"tiles {st.tiles_changed:3d}/{st.tiles_total}  "
              f"windows {st.windows_recomputed:5d}/{st.windows_total}  "
              f"levels {st.levels_active}/{st.levels_total}  "
              f"faces {len(rects)}")

    print("\n== concurrent streams through DetectorService ==")
    svc = DetectorService(det, ServiceConfig(
        pods=(PodSpec("big", 1.0), PodSpec("little", 0.4)),
        stream_config=StreamConfig(tile=20, threshold=0.0,
                                   keyframe_interval=8)))
    videos = [make_video("static_cctv", n_frames=6, h=160, w=160, seed=s)
              for s in (0, 1, 2)]
    sessions = [svc.open_stream() for _ in videos]
    reqs = [(sess.submit_frame(vid[t][0]))
            for t in range(6) for sess, vid in zip(sessions, videos)]
    svc.flush()
    for r in reqs:
        r.result()
    st = svc.stats()
    print(f"frames done: {st.stream.frames_done}  "
          f"modes: {st.stream.frame_modes}  "
          f"window skip: {st.stream.window_skip_frac:.2f}  "
          f"level skip: {st.stream.level_skip_frac:.2f}")
    print(f"p50 {st.latency_ms_p50:.1f} ms  p95 {st.latency_ms_p95:.1f} "
          f"ms  pods: {[(p.name, p.images) for p in st.pods]}")


if __name__ == "__main__":
    use_compile_cache()
    main()
