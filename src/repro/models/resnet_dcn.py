"""ResNet-50 backbone with deformable convolutional layers + detection head.

This is the paper's own model family: "Faster R-CNN ... which uses 12
DCLs in ResNet-50 as a backbone" (Sec. 4.1).  We reproduce the backbone
faithfully — the last ``num_dcn`` 3x3 convolutions of the c3/c4/c5
bottlenecks are replaced by DCLs (12 by default: c3's last 3, all 6 of
c4, all 3 of c5) — and attach a single-scale dense detection head
(objectness + class + box per stride-32 cell).  The two-stage Faster
R-CNN RPN/RoI machinery is out of scope for the accelerator study: the
paper's experiments hinge on the *offset statistics of the backbone
DCLs* under the Eq. 5 regularizer, which this model exposes per layer.

Norms are GroupNorm(32) (batch-stat-free, standard for detection).
Layout NHWC.  ``use_kernel=True`` routes every DCL through the Pallas
fused kernel (``repro.kernels.ops.deform_conv``) — including under
``jax.grad``: since PR 2 the bounded kernel path carries a custom VJP
(``kernels.deform_conv_bwd``), so ``train_loss`` with a kernel-path
config trains through the zero-copy Pallas dataflow in both
directions.  The pure-JAX path remains the parity reference.

Run eagerly (calibration, the reference tests), the forward opens a
program span (``repro.obs.trace``) around each piece of work:
``model/stem``, ``model/block``, ``model/head``, and inside them
``model/conv`` (an XLA conv with its weight cast), ``model/gn`` and
``model/dcl`` (the DCL with its int8 dequantize), each with a
``layer`` attribute such as ``s2b3/conv1``.  A profiler session then
puts every device program down to the layer that launched it.  When
the images, the params or the scale table are being traced
(``jax.jit``, ``jax.grad``, ``jax.vmap``; the serving engine's
program) the forward opens no span: it would time tracing, not work.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.deform_conv import conv2d
from repro.obs.trace import NOOP_SPAN, get_tracer
from .layers import ParamDef, dcl_apply, dcl_def

Array = jax.Array

GN_GROUPS = 32


@dataclasses.dataclass(frozen=True)
class ResNetDCNConfig:
    name: str = "resnet50_dcn"
    stage_sizes: tuple[int, ...] = (3, 4, 6, 3)
    widths: tuple[int, ...] = (256, 512, 1024, 2048)
    stem_width: int = 64
    num_dcn: int = 12              # last N 3x3 convs become DCLs
    offset_bound: float | None = None   # hardware-friendly clamp (Eq. 4)
    num_classes: int = 16
    img_size: int = 256
    dtype: Any = jnp.float32
    use_kernel: bool = False       # route DCLs through the Pallas kernel
    dataflow: str = "zero_copy"    # kernel dataflow: zero_copy | banded
    # DCL datapath: none | qat | int8 | int8_chain (int8_chain fuses the
    # offset conv in-kernel and emits the DCL output int8 on the
    # calibrated y_scale grid — 1 byte/elem through HBM, dequantized by
    # the consumer; with use_kernel=False the differentiable STE chain
    # reference runs instead, so chain configs train in the Trainer).
    quant: str = "none"
    bwd_cores: int = 1             # Megacore batch split of the bwd kernel
    # Data-parallel shard_map of the kernel path over the active mesh's
    # batch axes: None = auto (shard when a mesh is live and divides the
    # batch), True = require (ValueError otherwise), False = never.
    shard_batch: bool | None = None
    # Spatial shard_map of the kernel path: split the height axis over
    # the mesh axis mapped from logical "spatial" with a bounded halo
    # exchange per DCL (distributed.spatial).  None/False = off,
    # True = require (ValueError when no mesh / ragged heights).
    shard_spatial: bool | None = None

    @property
    def total_blocks(self) -> int:
        return sum(self.stage_sizes)

    def is_dcn(self, block_index: int) -> bool:
        """block_index counts bottleneck blocks from 0 (first c2 block)."""
        return block_index >= self.total_blocks - self.num_dcn


def _conv_def(kh, kw, cin, cout, *, scale=None):
    return ParamDef((kh, kw, cin, cout), (None, None, None, "conv_out"),
                    scale=scale)


def _gn_def(c):
    return {"scale": ParamDef((c,), (None,), init="ones"),
            "bias": ParamDef((c,), (None,), init="zeros")}


def group_norm(x: Array, params, *, groups: int = GN_GROUPS,
               eps: float = 1e-5) -> Array:
    n, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xf = x.astype(jnp.float32).reshape(n, h, w, g, c // g)
    mu = jnp.mean(xf, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xf, axis=(1, 2, 4), keepdims=True)
    y = ((xf - mu) * jax.lax.rsqrt(var + eps)).reshape(n, h, w, c)
    return (y * params["scale"] + params["bias"]).astype(x.dtype)


def _no_span(name: str, layer: str):
    return NOOP_SPAN


def _spans(*inputs):
    """``span(name, layer)`` for one forward: a program span around
    eager work, or none at all if any of ``inputs`` is being traced."""
    if any(isinstance(a, jax.core.Tracer)
           for a in jax.tree_util.tree_leaves(inputs)):
        return _no_span
    tracer = get_tracer()
    return lambda name, layer: tracer.span(name, layer=layer)


def _conv(span, x, w, layer: str, **kw):
    with span("model/conv", layer):
        return conv2d(x, w.astype(x.dtype), **kw)


def _gn(span, x, params, layer: str):
    with span("model/gn", layer):
        return group_norm(x, params)


def _dcl_def(cin, cout, k=3):
    return dcl_def(cin, cout, k)


def _block_def(cfg: ResNetDCNConfig, cin, width, block_index,
               *, downsample: bool):
    mid = width // 4
    d = {
        "conv1": _conv_def(1, 1, cin, mid), "gn1": _gn_def(mid),
        "gn2": _gn_def(mid),
        "conv3": _conv_def(1, 1, mid, width), "gn3": _gn_def(width),
    }
    if cfg.is_dcn(block_index):
        d["dcl"] = _dcl_def(mid, mid)
    else:
        d["conv2"] = _conv_def(3, 3, mid, mid)
    if downsample or cin != width:
        d["proj"] = _conv_def(1, 1, cin, width)
        d["gn_proj"] = _gn_def(width)
    return d


def model_def(cfg: ResNetDCNConfig) -> dict:
    defs: dict[str, Any] = {
        "stem": {"conv": _conv_def(7, 7, 3, cfg.stem_width),
                 "gn": _gn_def(cfg.stem_width)},
    }
    cin = cfg.stem_width
    bi = 0
    for s, (n_blocks, width) in enumerate(zip(cfg.stage_sizes, cfg.widths)):
        for b in range(n_blocks):
            defs[f"s{s}b{b}"] = _block_def(
                cfg, cin, width, bi, downsample=(b == 0))
            cin = width
            bi += 1
    c = cfg.widths[-1]
    defs["head"] = {
        "conv": _conv_def(3, 3, c, 256), "gn": _gn_def(256),
        "cls": _conv_def(1, 1, 256, cfg.num_classes + 1),   # +1 objectness
        "box": _conv_def(1, 1, 256, 4),
    }
    return defs


def init_params(key: Array, cfg: ResNetDCNConfig):
    from .layers import init_tree
    return init_tree(key, model_def(cfg))


def _apply_dcl(params, x: Array, cfg: ResNetDCNConfig, *, stride=1,
               quant_scales=None):
    return dcl_apply(params, x, stride=stride,
                     offset_bound=cfg.offset_bound,
                     use_kernel=cfg.use_kernel, dataflow=cfg.dataflow,
                     quant=cfg.quant, quant_scales=quant_scales,
                     cores=cfg.bwd_cores, shard_batch=cfg.shard_batch,
                     shard_spatial=cfg.shard_spatial, dtype=cfg.dtype)


def _apply_block(params, x: Array, cfg: ResNetDCNConfig, *, stride: int,
                 is_dcn: bool, name: str = "", tap=None, quant_scales=None,
                 span=_no_span):
    with span("model/block", name):
        h = _conv(span, x, params["conv1"], f"{name}/conv1")
        h = jax.nn.relu(_gn(span, h, params["gn1"], f"{name}/gn1"))
        o_max = None
        if is_dcn:
            if tap is not None:
                tap(name, h)
            with span("model/dcl", f"{name}/dcl"):
                h, o_max = _apply_dcl(params["dcl"], h, cfg, stride=stride,
                                      quant_scales=quant_scales)
                if hasattr(h, "dequantize"):
                    # int8_chain emission: the DCL output crossed HBM as
                    # int8 (QTensor); the GroupNorm consumer decodes it
                    # here — the only fp32 materialization of the tensor.
                    h = h.dequantize(cfg.dtype)
            if tap is not None:
                tap(f"{name}/out", h)
        else:
            h = _conv(span, h, params["conv2"], f"{name}/conv2",
                      stride=stride)
        h = jax.nn.relu(_gn(span, h, params["gn2"], f"{name}/gn2"))
        h = _conv(span, h, params["conv3"], f"{name}/conv3")
        h = _gn(span, h, params["gn3"], f"{name}/gn3")
        if "proj" in params:
            x = _conv(span, x, params["proj"], f"{name}/proj",
                      stride=stride)
            x = _gn(span, x, params["gn_proj"], f"{name}/gn_proj")
        return jax.nn.relu(x + h), o_max


def forward(params, cfg: ResNetDCNConfig, images: Array, *, tap=None,
            quant_scales=None):
    """images: (N, H, W, 3) -> (outputs, o_max dict per DCL).

    ``tap(name, x)`` is invoked with every DCL block's input activation
    — the calibration hook of ``repro.quant.calibrate`` (run eagerly so
    observers see concrete values).  ``quant_scales`` is a calibration
    scale table ({block_name: {"x_scale", "w_scale"}}) consumed when
    ``cfg.quant`` is ``"qat"``/``"int8"``; None = dynamic absmax.
    """
    span = _spans(params, images, quant_scales)
    with span("model/stem", "stem"):
        x = images.astype(cfg.dtype)
        x = _conv(span, x, params["stem"]["conv"], "stem/conv", stride=2,
                  padding=3)
        x = jax.nn.relu(_gn(span, x, params["stem"]["gn"], "stem/gn"))
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            [(0, 0), (1, 1), (1, 1), (0, 0)])

    o_maxes: dict[str, Array] = {}
    bi = 0
    for s, (n_blocks, width) in enumerate(zip(cfg.stage_sizes, cfg.widths)):
        for b in range(n_blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            name = f"s{s}b{b}"
            scales = quant_scales.get(name) if quant_scales else None
            x, o_max = _apply_block(params[name], x, cfg,
                                    stride=stride, is_dcn=cfg.is_dcn(bi),
                                    name=name, tap=tap,
                                    quant_scales=scales, span=span)
            if o_max is not None:
                o_maxes[name] = o_max
            bi += 1

    with span("model/head", "head"):
        h = _conv(span, x, params["head"]["conv"], "head/conv")
        h = jax.nn.relu(_gn(span, h, params["head"]["gn"], "head/gn"))
        cls = _conv(span, h, params["head"]["cls"], "head/cls")
        box = _conv(span, h, params["head"]["box"], "head/box")
    return {"cls": cls, "box": box, "features": x}, o_maxes


def detection_loss(outputs: dict, targets: dict) -> tuple[Array, dict]:
    """Dense single-scale detection loss.

    targets: obj (N,Hc,Wc) {0,1}, cls (N,Hc,Wc) int, box (N,Hc,Wc,4).
    Classification: sigmoid BCE on objectness + CE on class for positive
    cells; box: L1 on positive cells.
    """
    cls_logits = outputs["cls"].astype(jnp.float32)
    box_pred = outputs["box"].astype(jnp.float32)
    obj_logit = cls_logits[..., 0]
    cls_logit = cls_logits[..., 1:]
    obj = targets["obj"].astype(jnp.float32)

    bce = jnp.mean(
        jnp.maximum(obj_logit, 0) - obj_logit * obj
        + jnp.log1p(jnp.exp(-jnp.abs(obj_logit))))

    pos = obj
    n_pos = jnp.maximum(jnp.sum(pos), 1.0)
    logp = jax.nn.log_softmax(cls_logit, axis=-1)
    gold = jnp.take_along_axis(logp, targets["cls"][..., None], axis=-1)[..., 0]
    ce = -jnp.sum(gold * pos) / n_pos
    l1 = jnp.sum(jnp.abs(box_pred - targets["box"]) * pos[..., None]) / n_pos
    loss = bce + ce + 0.5 * l1
    return loss, {"bce": bce, "ce": ce, "l1": l1}


def train_loss(params, cfg: ResNetDCNConfig, batch: dict, *,
               lam: float = 0.0, smoothness: float = 0.0,
               quant_scales=None):
    """Full paper objective: Eq. 5 over the detection loss.  With
    ``cfg.quant="qat"`` this is the quantization-aware objective —
    fake-quant DCLs (STE) under the same Eq. 5 regularizer, trainable
    by the production ``Trainer`` unchanged."""
    from repro.core.rf_regularizer import regularized_loss
    outputs, o_maxes = forward(params, cfg, batch["images"],
                               quant_scales=quant_scales)
    task, metrics = detection_loss(outputs, batch)
    if lam > 0.0 and o_maxes:
        loss = regularized_loss(task, list(o_maxes.values()), lam,
                                smoothness=smoothness)
    else:
        loss = task
    metrics = dict(metrics)
    if o_maxes:
        metrics["o_max"] = jnp.max(jnp.stack(list(o_maxes.values())))
    return loss, metrics
