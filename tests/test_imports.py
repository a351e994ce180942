"""Import sweep: every public module must import cleanly.

Role of the reference's ``tests/docker_extension_builds/run.sh`` (verifies
each optional extension builds): here each subpackage — including every
contrib extension and the C++-backed native module — must import and expose
its ``__all__`` names.
"""

import importlib
import pkgutil

import pytest

import apex_tpu

MODULES = [
    "apex_tpu",
    "apex_tpu.amp",
    "apex_tpu.analysis",
    "apex_tpu.analysis.rules",
    "apex_tpu.checkpoint",
    "apex_tpu.checkpoint.manifest",
    "apex_tpu.checkpoint.retry",
    "apex_tpu.checkpoint.sharded",
    "apex_tpu.checkpoint.verify",
    "apex_tpu.data",
    "apex_tpu.fp16_utils",
    "apex_tpu.fused_dense",
    "apex_tpu.loadtest",
    "apex_tpu.lora",
    "apex_tpu.mlp",
    "apex_tpu.monitor",
    "apex_tpu.multi_tensor_apply",
    "apex_tpu.native",
    "apex_tpu.normalization",
    "apex_tpu.observability",
    "apex_tpu.observability.fleet_metrics",
    "apex_tpu.observability.slo",
    "apex_tpu.observability.trace",
    "apex_tpu.ops",
    "apex_tpu.ops.decode_attention",
    "apex_tpu.ops.grouped_matmul",
    "apex_tpu.optimizers",
    "apex_tpu.parallel",
    "apex_tpu.parallel.multiproc",
    "apex_tpu.resilience",
    "apex_tpu.rnn",
    "apex_tpu.serving",
    "apex_tpu.serving.fleet",
    "apex_tpu.serving.prefix",
    "apex_tpu.serving.speculation",
    "apex_tpu.testing_faults",
    "apex_tpu.training",
    "apex_tpu.transformer",
    "apex_tpu.transformer.amp",
    "apex_tpu.transformer.moe",
    "apex_tpu.transformer.parallel_state",
    "apex_tpu.transformer.pipeline_parallel",
    "apex_tpu.transformer.tensor_parallel",
    "apex_tpu.transformer.tensor_parallel.memory",
    "apex_tpu.transformer.testing",
    "apex_tpu.transformer._data",
    "apex_tpu.utils",
    "apex_tpu.models",
    "apex_tpu.contrib",
    "apex_tpu.contrib.bottleneck",
    "apex_tpu.contrib.clip_grad",
    "apex_tpu.contrib.conv_bias_relu",
    "apex_tpu.contrib.cudnn_gbn",
    "apex_tpu.contrib.fmha",
    "apex_tpu.contrib.focal_loss",
    "apex_tpu.contrib.gpu_direct_storage",
    "apex_tpu.contrib.group_norm",
    "apex_tpu.contrib.groupbn",
    "apex_tpu.contrib.index_mul_2d",
    "apex_tpu.contrib.layer_norm",
    "apex_tpu.contrib.multihead_attn",
    "apex_tpu.contrib.openfold",
    "apex_tpu.contrib.peer_memory",
    "apex_tpu.contrib.sparsity",
    "apex_tpu.contrib.transducer",
    "apex_tpu.contrib.xentropy",
]


@pytest.mark.parametrize("name", MODULES)
def test_imports(name):
    mod = importlib.import_module(name)
    for sym in getattr(mod, "__all__", []):
        assert hasattr(mod, sym), f"{name}.__all__ lists missing {sym!r}"


def test_no_unlisted_packages():
    """Every subpackage on disk is in the sweep (catches future additions)."""
    found = {
        name
        for _, name, _ in pkgutil.walk_packages(
            apex_tpu.__path__, prefix="apex_tpu.")
    }
    packages = {n for n in found if not n.rsplit(".", 1)[-1].startswith("_")}
    swept = set(MODULES)
    # sweep granularity: top-level subpackages + immediate contrib children
    # (their internal modules are covered transitively by the package import)
    top_and_contrib = {
        n for n in packages
        if n.count(".") == 1
        or (n.startswith("apex_tpu.contrib.") and n.count(".") == 2)
    }
    missing = {n for n in top_and_contrib if n not in swept
               and not any(s.startswith(n + ".") or s == n for s in swept)}
    assert not missing, f"unswept subpackages: {sorted(missing)}"
