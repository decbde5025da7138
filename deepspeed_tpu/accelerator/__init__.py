from .abstract_accelerator import DeepSpeedAccelerator  # noqa: F401
from .real_accelerator import (device_platform, get_accelerator,  # noqa: F401
                               on_tpu, set_accelerator)
