"""Plain reference of the ResNet-DCN detector, in jax.numpy and float32.

It imports nothing of the system under test.  It follows the published
model (ResNet-50 bottlenecks, Dai et al. 2017 deformable convolutions
in the last ``num_dcn`` 3x3 convolutions, GroupNorm(32), a dense
single-scale head) with the semantics the served model states:

* NHWC activations, HWIO weights; plain convolutions pad "SAME", the
  stem pads 3, the offset convolution of a DCL pads 1 at its stride.
* A DCL's offsets are 2*K*K channels, (dy, dx) per tap in row-major
  tap order; with an offset bound B they are clamped to [-B, B].
  Sampling is bilinear against a zero-padded plane.
* ``dcl="fp32"``: everything in float32.  ``precision="highest"``
  computes every float convolution and contraction exactly (HIGHEST);
  ``"high"`` computes them in three bf16 passes (hi*hi + hi*lo +
  lo*hi of each operand split into two bf16 parts, summed in float32),
  as the TPU's ``high`` precision does, on any platform: the control
  one precision below what the configuration states.
* ``dcl="int8"`` / ``"int4"``: each DCL runs the chained integer
  datapath on the calibrated grids (symmetric, round half to even):
  the input is quantized onto ``x_scale``, offsets come from the
  integer offset convolution dequantized by ``x_scale * w_offset_scale``
  plus the bias, the bilinear mix of the integer plane is rounded back
  onto the grid, the contraction with the integer weights is exact, and
  the output is requantized onto ``y_scale`` with the bias folded in,
  then dequantized for the GroupNorm that follows.  ``"int4"`` keeps
  the same absolute ranges on 7 levels a side: the control that a
  lower precision than the configuration states must fail.

Departure from the paper: the detector's two-stage Faster R-CNN head
is the served model's dense head (objectness + 80 classes, 4 box
coordinates per stride-32 cell); the backbone is the published one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
QMAX = {"int8": 127.0, "int4": 7.0}
EPS = 1e-12               # least absolute maximum a scale is taken from
K = 3                      # every DCL of the family is 3x3
GN_GROUPS = 32
GN_EPS = 1e-5


def blocks(cfg):
    """Yields (name, cin, width, stride, is_dcn) for every bottleneck."""
    total = sum(cfg["stage_sizes"])
    cin, bi = cfg["stem_width"], 0
    for s, (n, width) in enumerate(zip(cfg["stage_sizes"], cfg["widths"])):
        for b in range(n):
            stride = 2 if (b == 0 and s > 0) else 1
            yield f"s{s}b{b}", cin, width, stride, bi >= total - cfg["num_dcn"]
            cin, bi = width, bi + 1


def _split(a):
    """a = hi + lo + (what three bf16 passes drop), hi and lo bf16."""
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def three_pass(op, x, w):
    """``op`` (bilinear in x and w) in three bf16 passes."""
    (xh, xl), (wh, wl) = _split(x), _split(w)
    return op(xh, wh) + op(xh, wl) + op(xl, wh)


def _conv(x, w, stride=1, pad="SAME", precision="highest",
          preferred_element_type=None):
    if isinstance(pad, int):
        pad = [(pad, pad), (pad, pad)]

    def op(a, b, prec=HIGHEST):
        return lax.conv_general_dilated(
            a, b, (stride, stride), pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec,
            preferred_element_type=preferred_element_type)
    if precision == "high":
        return three_pass(op, x, w)
    return op(x, w, HIGHEST if precision == "highest" else precision)


def group_norm(x, p):
    n, h, w, c = x.shape
    g = min(GN_GROUPS, c)
    while c % g:
        g -= 1
    xg = x.reshape(n, h, w, g, c // g)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    y = ((xg - mean) / jnp.sqrt(var + GN_EPS)).reshape(n, h, w, c)
    return y * p["scale"] + p["bias"]


def bilinear_taps(x, offsets, stride):
    """x (N, H, W, C), offsets (N, Ho, Wo, K*K, 2) -> the sampled taps
    (N, Ho, Wo, K*K, C): four corners weighted by the bilinear tent,
    summed in the order (y0,x0), (y0,x1), (y1,x0), (y1,x1)."""
    n, h, w, c = x.shape
    _, ho, wo, _, _ = offsets.shape
    ky, kx = jnp.divmod(jnp.arange(K * K), K)
    py = (jnp.arange(ho)[:, None, None] * stride - 1 + ky[None, None, :]
          + offsets[..., 0])
    px = (jnp.arange(wo)[None, :, None] * stride - 1 + kx[None, None, :]
          + offsets[..., 1])
    y0, x0 = jnp.floor(py), jnp.floor(px)
    fy, fx = py - y0, px - x0
    y0, x0 = y0.astype(jnp.int32), x0.astype(jnp.int32)
    batch = jnp.arange(n)[:, None, None, None]
    out = None
    for dy, dx, wgt in ((0, 0, (1 - fy) * (1 - fx)), (0, 1, (1 - fy) * fx),
                        (1, 0, fy * (1 - fx)), (1, 1, fy * fx)):
        yy, xx = y0 + dy, x0 + dx
        inside = (yy >= 0) & (yy < h) & (xx >= 0) & (xx < w)
        v = x[batch, jnp.clip(yy, 0, h - 1), jnp.clip(xx, 0, w - 1)]
        term = v * jnp.where(inside, wgt, 0.0)[..., None]
        out = term if out is None else out + term
    return out


def dcl_fp32(p, x, stride, bound, precision="highest"):
    off = _conv(x, p["w_offset"], stride, pad=1, precision=precision) \
        + p["b_offset"]
    n, ho, wo, _ = off.shape
    off = off.reshape(n, ho, wo, K * K, 2)
    if bound is not None:
        off = jnp.clip(off, -bound, bound)
    taps = bilinear_taps(x, off, stride)
    c, m = p["w_deform"].shape[2:]
    w = p["w_deform"].reshape(K * K, c, m)

    def op(a, b):
        return jnp.einsum("nhwkc,kcm->nhwm", a, b, precision=HIGHEST)
    y = three_pass(op, taps, w) if precision == "high" else op(taps, w)
    return y + p["b_deform"]


def _grid(v, qmax):
    return jnp.clip(jnp.round(v), -qmax, qmax)


def dcl_int(p, x, s, stride, bound, bits="int8"):
    """One chained integer DCL.  Returns (int-valued output, its scale):
    ``out * scale`` is the dequantized output."""
    qmax = QMAX[bits]
    wide = 127.0 / qmax                  # same ranges, fewer levels
    sx = jnp.asarray(s["x_scale"], jnp.float32) * wide
    sy = jnp.asarray(s["y_scale"], jnp.float32) * wide
    sw = jnp.asarray(s["w_scale"], jnp.float32) * wide
    swo = jnp.asarray(s["w_offset_scale"], jnp.float32) * wide
    c, m = p["w_deform"].shape[2:]
    xq = _grid(x / sx, qmax)
    wq = _grid(p["w_deform"] / sw, qmax)
    woq = _grid(p["w_offset"] / swo, qmax)
    acc = _conv(xq.astype(jnp.int8), woq.astype(jnp.int8), stride, pad=1,
                precision=lax.Precision.DEFAULT,
                preferred_element_type=jnp.int32)
    off = acc.astype(jnp.float32) * (sx * swo) + p["b_offset"]
    n, ho, wo, _ = off.shape
    off = off.reshape(n, ho, wo, K * K, 2)
    if bound is not None:
        off = jnp.clip(off, -bound, bound)
    taps = jnp.round(bilinear_taps(xq, off, stride))
    acc = jnp.einsum("nhwkc,kcm->nhwm", taps.astype(jnp.int8),
                     wq.reshape(K * K, c, m).astype(jnp.int8),
                     precision=lax.Precision.DEFAULT,
                     preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * ((sx * sw) / sy) + p["b_deform"] / sy
    return _grid(y, qmax), sy


def forward(params, cfg, images, *, dcl="fp32", scales=None, tap=None,
            precision="highest"):
    """images (N, H, W, 3) -> {"cls", "box"}.  ``tap(name, x)`` sees each
    DCL's input and (``name + "/out"``) its dequantized output.  Every
    float convolution and contraction runs at ``precision``."""
    bound = cfg["offset_bound"]

    def conv(x, w, stride=1, pad="SAME"):
        return _conv(x, w, stride, pad, precision=precision)
    x = conv(images, params["stem"]["conv"], 2, pad=3)
    x = jax.nn.relu(group_norm(x, params["stem"]["gn"]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    for name, _, _, stride, is_dcn in blocks(cfg):
        p = params[name]
        h = jax.nn.relu(group_norm(conv(x, p["conv1"]), p["gn1"]))
        if is_dcn:
            if tap is not None:
                tap(name, h)
            if dcl == "fp32":
                h = dcl_fp32(p["dcl"], h, stride, bound, precision)
            else:
                q, sy = dcl_int(p["dcl"], h, scales[name], stride, bound,
                                bits=dcl)
                h = q * sy
            if tap is not None:
                tap(f"{name}/out", h)
        else:
            h = conv(h, p["conv2"], stride)
        h = jax.nn.relu(group_norm(h, p["gn2"]))
        h = group_norm(conv(h, p["conv3"]), p["gn3"])
        if "proj" in p:
            x = group_norm(conv(x, p["proj"], stride), p["gn_proj"])
        x = jax.nn.relu(x + h)
    hd = params["head"]
    h = jax.nn.relu(group_norm(conv(x, hd["conv"]), hd["gn"]))
    return {"cls": conv(h, hd["cls"]), "box": conv(h, hd["box"])}
