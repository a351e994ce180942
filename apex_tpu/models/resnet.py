"""ResNet family (ResNet-18/34/50/101/152), TPU-native NHWC.

Capability counterpart of the reference's flagship example — ResNet-50
ImageNet training under amp O2 + apex DDP
(``/root/reference/examples/imagenet/main_amp.py``; the model itself comes
from torchvision there, but the *capability* — a convnet exercising amp,
SyncBN (``apex/parallel/optimized_sync_batchnorm.py``), fused optimizers and
data parallelism — is apex's headline configuration).

TPU design (not a port):

- NHWC layout end-to-end: the layout the MXU conv units want, which the
  reference's ``--channels-last`` / NHWC contrib kernels
  (``apex/contrib/groupbn``) fight torch to get.
- functional module protocol matching the rest of the model zoo:
  ``init(key) -> (params, state)``, ``apply(params, state, x, train=...)``
  returning ``(logits, new_state)`` — batch statistics are explicit carried
  state, never Python-side mutation, so the whole train step jits.
- BatchNorm is synchronized over the data axis when ``axis_name`` is bound
  (inside ``shard_map``): local sums are ``psum``-merged before normalizing,
  the same Welford-merge semantics as the reference's
  ``optimized_sync_batchnorm_kernel.py:7-120`` / ``csrc/welford.cu``. Under
  plain pjit/GSPMD the global batch mean is already synchronized — XLA
  inserts the collective.
- bf16 compute with fp32 BN statistics and fp32 residual accumulation is the
  amp-O2 equivalent (policy applied by the caller via
  :mod:`apex_tpu.amp`); params stay fp32.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from apex_tpu.utils.batch_norm import (bn_apply as _bn_apply,
                                       bn_from_sums as _bn_from_sums,
                                       bn_init as _bn_init,
                                       bn_sums as _bn_sums)
from apex_tpu.utils.conv import conv_nhwc as _conv, he_init as _he_init

__all__ = ["ResNetConfig", "ResNet", "resnet18", "resnet34", "resnet50",
           "resnet101", "resnet152"]

# (block type, per-stage block counts) keyed by depth — torchvision layout,
# which examples/imagenet/main_amp.py consumes via `models.__dict__[arch]`.
_DEPTHS = {
    18: ("basic", (2, 2, 2, 2)),
    34: ("basic", (3, 4, 6, 3)),
    50: ("bottleneck", (3, 4, 6, 3)),
    101: ("bottleneck", (3, 4, 23, 3)),
    152: ("bottleneck", (3, 8, 36, 3)),
}

_STAGE_WIDTHS = (64, 128, 256, 512)


@dataclass(frozen=True)
class ResNetConfig:
    depth: int = 50
    num_classes: int = 1000
    width: int = 64                    # stem width
    bn_momentum: float = 0.1
    bn_eps: float = 1e-5
    # data-parallel axis to synchronize BN stats over (None = local/GSPMD)
    axis_name: Optional[str] = None
    compute_dtype: Any = jnp.float32   # bf16 = the amp-O2 cast
    # zero-init the last BN scale of each residual block (torchvision
    # `zero_init_residual`, the standard large-batch RN50 recipe)
    zero_init_residual: bool = True
    # route bottleneck 1x1 convs through the fused Pallas GEMM+BN+stats
    # kernel (ops/conv_fused.py) during training — folds the separate BN
    # statistics and normalize passes into the conv's own HBM streams.
    # Opt-in (None = off): per-op the kernels beat XLA's backward, but at
    # the whole-model level XLA's convs and Pallas disagree on activation
    # layouts, and the boundary copies outweigh the win on v5e (measured
    # analysis in PERF.md — the same reason the reference ships its fused
    # bottleneck as opt-in contrib, bottleneck.py:134).
    fused_conv: Optional[bool] = None

    @property
    def block(self) -> str:
        return _DEPTHS[self.depth][0]

    @property
    def stage_blocks(self) -> Tuple[int, ...]:
        return _DEPTHS[self.depth][1]

    @property
    def expansion(self) -> int:
        return 4 if self.block == "bottleneck" else 1


class ResNet:
    """Functional ResNet. ``init(key) -> (params, state)``;
    ``apply(params, state, x_nhwc, train) -> (logits, new_state)``."""

    def __init__(self, config: ResNetConfig):
        self.config = config

    # -- init ----------------------------------------------------------------

    def _block_init(self, key, cin, width, cout, stride):
        cfg = self.config
        ks = jax.random.split(key, 4)
        p: Dict[str, Any] = {}
        st: Dict[str, Any] = {}
        if cfg.block == "bottleneck":
            convs = [("conv1", (1, 1, cin, width), 1),
                     ("conv2", (3, 3, width, width), stride),
                     ("conv3", (1, 1, width, cout), 1)]
        else:
            convs = [("conv1", (3, 3, cin, width), stride),
                     ("conv2", (3, 3, width, cout), 1)]
        for i, (name, shape, _) in enumerate(convs):
            p[name] = _he_init(ks[i], shape)
            bnp, bns = _bn_init(shape[-1])
            p[f"bn{i + 1}"], st[f"bn{i + 1}"] = bnp, bns
        if cfg.zero_init_residual:
            last = f"bn{len(convs)}"
            p[last] = dict(p[last], scale=jnp.zeros_like(p[last]["scale"]))
        if stride != 1 or cin != cout:
            p["down_conv"] = _he_init(ks[3], (1, 1, cin, cout))
            p["down_bn"], st["down_bn"] = _bn_init(cout)
        return p, st

    def init(self, key: jax.Array):
        cfg = self.config
        keys = jax.random.split(key, 2 + len(cfg.stage_blocks))
        params: Dict[str, Any] = {
            "stem": {"conv": _he_init(keys[0], (7, 7, 3, cfg.width))}}
        state: Dict[str, Any] = {"stem": {}}
        params["stem"]["bn"], state["stem"]["bn"] = _bn_init(cfg.width)
        cin = cfg.width
        for si, nblocks in enumerate(cfg.stage_blocks):
            width = _STAGE_WIDTHS[si]
            cout = width * cfg.expansion
            bkeys = jax.random.split(keys[1 + si], nblocks)
            stage_p, stage_s = [], []
            for bi in range(nblocks):
                stride = 2 if (si > 0 and bi == 0) else 1
                bp, bs = self._block_init(bkeys[bi], cin, width, cout, stride)
                stage_p.append(bp)
                stage_s.append(bs)
                cin = cout
            params[f"layer{si + 1}"] = stage_p
            state[f"layer{si + 1}"] = stage_s
        fan_in = cin
        params["fc"] = {
            "kernel": jax.random.normal(keys[-1], (fan_in, cfg.num_classes),
                                        jnp.float32) * fan_in ** -0.5,
            "bias": jnp.zeros((cfg.num_classes,), jnp.float32),
        }
        return params, state

    def spec(self):
        """Replicated params (pure DP); shard the batch dim of inputs."""
        params, state = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        rep = lambda tree: jax.tree_util.tree_map(
            lambda _: PartitionSpec(), tree)
        return rep(params), rep(state)

    # -- apply ---------------------------------------------------------------

    def _bn(self, p, s, x, train):
        cfg = self.config
        return _bn_apply(p, s, x, train=train, momentum=cfg.bn_momentum,
                         eps=cfg.bn_eps, axis_name=cfg.axis_name)

    def _use_fused(self) -> bool:
        return bool(self.config.fused_conv)

    def _block_apply_fused(self, p, s, x, stride):
        """Bottleneck block on the fused 1x1-GEMM+BN kernels (training hot
        path): each 1x1 conv reads its raw input once, applies the previous
        BN's normalize+ReLU on the fly, and emits its output's batch
        statistics from a VMEM epilogue — the TPU counterpart of the
        reference's fused bottleneck graphs
        (``apex/contrib/bottleneck/bottleneck.py:134-262``). The 3x3 conv
        stays an XLA convolution (its input normalize fuses into the conv
        read; its output statistics are one fused reduction pass)."""
        cfg = self.config
        from apex_tpu.ops.conv_fused import conv1x1_bn_act, conv3x3_bn_act
        new_s = {}

        def close(bn_name, sums, n, y=None):
            """bn_from_sums (+ optionally the normalize affine in the
            activation dtype); records the updated running stats."""
            a, b, new_s[bn_name] = _bn_from_sums(
                p[bn_name], s[bn_name], sums, n, shift=s[bn_name]["mean"],
                momentum=cfg.bn_momentum, eps=cfg.bn_eps,
                axis_name=cfg.axis_name)
            if y is None:
                return a, b
            return y * a.astype(y.dtype) + b.astype(y.dtype)

        nhw = x.shape[0] * x.shape[1] * x.shape[2]
        y1, s1 = conv1x1_bn_act(x, p["conv1"].reshape(x.shape[-1], -1),
                                stats_shift=s["bn1"]["mean"])
        a1, b1 = close("bn1", s1, nhw)
        if stride == 1:
            # fused 3x3: bn1 normalize+relu on the fly, stats epilogue
            y2, s2 = conv3x3_bn_act(y1, p["conv2"], a1, b1, relu=True,
                                    stats_shift=s["bn2"]["mean"])
            nhw2 = nhw
        else:
            # the 3 stride-2 blocks keep the XLA conv (strided slicing in
            # the shifted-GEMM kernel costs more than the boundary copy)
            z1 = jax.nn.relu(y1 * a1.astype(y1.dtype)
                             + b1.astype(y1.dtype))
            y2 = _conv(z1, p["conv2"], stride)
            s2 = _bn_sums(y2, s["bn2"]["mean"])
            nhw2 = y2.shape[0] * y2.shape[1] * y2.shape[2]
        a2, b2 = close("bn2", s2, nhw2)
        y3, s3 = conv1x1_bn_act(y2, p["conv3"].reshape(y2.shape[-1], -1),
                                a2, b2, relu=True,
                                stats_shift=s["bn3"]["mean"])
        out = close("bn3", s3, nhw2, y3)
        if "down_conv" in p:
            xd = x[:, ::stride, ::stride, :] if stride != 1 else x
            yd, sd = conv1x1_bn_act(xd,
                                    p["down_conv"].reshape(x.shape[-1], -1),
                                    stats_shift=s["down_bn"]["mean"])
            residual = close("down_bn", sd, nhw2, yd)
        else:
            residual = x
        return jax.nn.relu(out + residual), new_s

    def _block_apply(self, p, s, x, stride, train):
        cfg = self.config
        if cfg.block == "bottleneck" and train and self._use_fused():
            return self._block_apply_fused(p, s, x, stride)
        new_s = {}
        out = _conv(x, p["conv1"], stride if cfg.block == "basic" else 1)
        out, new_s["bn1"] = self._bn(p["bn1"], s["bn1"], out, train)
        out = jax.nn.relu(out)
        out = _conv(out, p["conv2"], 1 if cfg.block == "basic" else stride)
        out, new_s["bn2"] = self._bn(p["bn2"], s["bn2"], out, train)
        if cfg.block == "bottleneck":
            out = jax.nn.relu(out)
            out = _conv(out, p["conv3"])
            out, new_s["bn3"] = self._bn(p["bn3"], s["bn3"], out, train)
        if "down_conv" in p:
            residual = _conv(x, p["down_conv"], stride)
            residual, new_s["down_bn"] = self._bn(
                p["down_bn"], s["down_bn"], residual, train)
        else:
            residual = x
        return jax.nn.relu(out + residual), new_s

    def apply(self, params, state, x, *, train: bool = False):
        """x: [N, H, W, 3] NHWC, any float dtype; returns fp32 logits."""
        cfg = self.config
        x = x.astype(cfg.compute_dtype)
        new_state: Dict[str, Any] = {"stem": {}}
        out = _conv(x, params["stem"]["conv"].astype(cfg.compute_dtype),
                    stride=2)
        out, new_state["stem"]["bn"] = self._bn(
            params["stem"]["bn"], state["stem"]["bn"], out, train)
        out = jax.nn.relu(out)
        out = lax.reduce_window(
            out, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
        for si, nblocks in enumerate(cfg.stage_blocks):
            stage_p = params[f"layer{si + 1}"]
            stage_s = state[f"layer{si + 1}"]
            new_stage = []
            for bi in range(nblocks):
                stride = 2 if (si > 0 and bi == 0) else 1
                bp = jax.tree_util.tree_map(
                    lambda a: a.astype(cfg.compute_dtype)
                    if a.ndim == 4 else a, stage_p[bi])
                out, bs = self._block_apply(bp, stage_s[bi], out, stride,
                                            train)
                new_stage.append(bs)
            new_state[f"layer{si + 1}"] = new_stage
        out = jnp.mean(out.astype(jnp.float32), axis=(1, 2))
        logits = out @ params["fc"]["kernel"] + params["fc"]["bias"]
        return logits, new_state


def _make(depth):
    def ctor(**kw) -> ResNet:
        return ResNet(ResNetConfig(depth=depth, **kw))
    ctor.__name__ = f"resnet{depth}"
    ctor.__doc__ = f"ResNet-{depth} (torchvision-equivalent topology)."
    return ctor


resnet18 = _make(18)
resnet34 = _make(34)
resnet50 = _make(50)
resnet101 = _make(101)
resnet152 = _make(152)
