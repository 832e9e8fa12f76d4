"""Plain reference of the ResNet-DCNv2 detector, in jax.numpy and float32.

It imports nothing of the system under test.  The network is
``bench/reference/resnet_dcn.py``'s (its helpers are reused: blocks,
convolutions, GroupNorm, bilinear taps, head); only the DCL differs.
A modulated deformable convolution (Zhu et al. 2019, arXiv:1811.11168,
Eq. 3) computes

    y(p) = sum_k w_k * x(p + p_k + dp_k) * dm_k

* the offset convolution has 3*K*K outputs: channels [0, 2*K*K) are
  the offsets, (dy, dx) per tap in row-major tap order, and channels
  [2*K*K, 3*K*K) are mask logits, one per tap; dm_k = sigmoid(logit);
* with an offset bound B the offsets (never the masks) are clamped to
  [-B, B];
* each bilinear tap is multiplied by its mask before the contraction;
  on the integer datapaths (``dcl_int``) the masked mix of the integer
  plane is what is rounded back onto the grid (a mask in [0, 1] keeps
  it in range).

Everything else (precisions, the integer grids, the int4 control) is as
the DCNv1 reference states.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference.resnet_dcn import (EPS, HIGHEST, K, QMAX,  # noqa: F401
                                        _conv, _grid, bilinear_taps, blocks,
                                        group_norm, three_pass)


def _offsets_and_mask(o, bound):
    """(clamped offsets (N, Ho, Wo, K*K, 2), mask (N, Ho, Wo, K*K)) of
    an offset-conv output with 3*K*K channels."""
    n, ho, wo, _ = o.shape
    off = o[..., :2 * K * K].reshape(n, ho, wo, K * K, 2)
    if bound is not None:
        off = jnp.clip(off, -bound, bound)
    return off, jax.nn.sigmoid(o[..., 2 * K * K:])


def dcl_fp32(p, x, stride, bound, precision="highest"):
    o = _conv(x, p["w_offset"], stride, pad=1, precision=precision) \
        + p["b_offset"]
    off, mask = _offsets_and_mask(o, bound)
    taps = bilinear_taps(x, off, stride) * mask[..., None]
    c, m = p["w_deform"].shape[2:]
    w = p["w_deform"].reshape(K * K, c, m)

    def op(a, b):
        return jnp.einsum("nhwkc,kcm->nhwm", a, b, precision=HIGHEST)
    y = three_pass(op, taps, w) if precision == "high" else op(taps, w)
    return y + p["b_deform"]


def dcl_int(p, x, s, stride, bound, bits="int8"):
    """One chained integer modulated DCL.  Returns (int-valued output,
    its scale): ``out * scale`` is the dequantized output."""
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
    o = acc.astype(jnp.float32) * (sx * swo) + p["b_offset"]
    off, mask = _offsets_and_mask(o, bound)
    taps = jnp.round(bilinear_taps(xq, off, stride) * mask[..., None])
    acc = jnp.einsum("nhwkc,kcm->nhwm", taps.astype(jnp.int8),
                     wq.reshape(K * K, c, m).astype(jnp.int8),
                     precision=lax.Precision.DEFAULT,
                     preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * ((sx * sw) / sy) + p["b_deform"] / sy
    return _grid(y, qmax), sy


def forward(params, cfg, images, *, dcl="fp32", scales=None, tap=None,
            precision="highest"):
    """images (N, H, W, 3) -> {"cls", "box"}, as
    ``resnet_dcn.forward`` with modulated DCLs.  ``tap(name, x)`` sees
    each DCL's input and (``name + "/out"``) its dequantized output."""
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
