from apex_tpu.utils.logging import get_logger, RankInfoFormatter
from apex_tpu.utils.deprecation import deprecated_warning
from apex_tpu.utils.flops import (
    peak_flops_per_chip,
    resnet50_train_flops,
    transformer_train_flops,
)
from apex_tpu.utils.profiling import (
    device_memory_stats,
    nvtx_range,
    profiler_start,
    profiler_stop,
)
from apex_tpu.utils.tree import (
    tree_cast,
    tree_size,
    tree_zeros_like,
    global_norm,
)

__all__ = [
    "get_logger",
    "RankInfoFormatter",
    "deprecated_warning",
    "tree_cast",
    "tree_size",
    "tree_zeros_like",
    "global_norm",
    "nvtx_range",
    "profiler_start",
    "profiler_stop",
    "device_memory_stats",
    "peak_flops_per_chip",
    "resnet50_train_flops",
    "transformer_train_flops",
]
