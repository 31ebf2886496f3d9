# The paper's primary contribution: cascading-classifier face detection
# restructured for wide-SIMD execution (dense/delayed-rejection baseline +
# wave-compaction engine), plus the asymmetric scheduling / energy layer in
# repro.scheduling.
from .cascade import (Cascade, WINDOW, make_cascade, save_cascade,  # noqa: F401
                      load_cascade, paper_shaped_cascade, PAPER_STAGE_SIZES)
from .integral import (integral_image, integral_images, rect_sum,  # noqa: F401
                       window_inv_sigma, integral_value)
from .engine import (Detector, EngineConfig, BatchResult,  # noqa: F401
                     CapacityOverflow, LevelResult, calibrate_capacities)
from .pyramid import pyramid_plan, build_pyramid, downscale_nearest  # noqa: F401
from .nms import group_rectangles, group_rectangles_batch, iou_matrix  # noqa: F401
