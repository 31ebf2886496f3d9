# Streaming video detection: temporal tile-reuse over the batched cascade
# engine (ROADMAP "video/streaming workload"; the paper's RIT relation says
# cascade work tracks content — unchanged content across frames is work the
# device stream step skips).
from .engine import (StreamEngine, StreamGeometry,  # noqa: F401
                     StreamState, StreamStepOut)
from .video import (StreamConfig, FrameStats,  # noqa: F401
                    VideoDetector, level_windows_from_raw)
from .synthetic import make_video, SCENARIOS  # noqa: F401
