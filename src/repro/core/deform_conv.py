"""Deformable convolution (Dai et al., ICCV'17) in pure JAX — the paper's Eq. 1-4.

This is the *reference* implementation of the deformable convolutional
layer (DCL) analysed by the paper:

    o = f(x, w_o)                      (Eq. 1)  -- offset-generating conv
    y = f(g(x, o), w_deform)           (Eq. 2)  -- conv over bilinearly
                                                   sampled inputs
    o_max = max_i |o_i|                (Eq. 3)
    RF    = K_C + 2 * ceil(o_max)      (Eq. 4)

Layout is NHWC (TPU-native).  Offsets are stored as (..., K*K, 2) with
``[..., 0] = dy`` and ``[..., 1] = dx`` relative to the regular grid tap
position.  Sampling outside the image contributes zero (standard DCN
semantics, matching bilinear interpolation against a zero-padded plane).

The hardware-friendly mode of the paper (bounded receptive field after
training with the Eq. 5 regularizer) is exposed via ``offset_bound``:
offsets are clamped to ``[-B, B]`` so the receptive field is statically
``K + 2*ceil(B)`` — this is what lets the Pallas kernels in
``repro.kernels`` use a fixed VMEM halo.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array


# ---------------------------------------------------------------------------
# Receptive-field algebra (Eq. 3, Eq. 4)
# ---------------------------------------------------------------------------

def offset_abs_max(offsets: Array) -> Array:
    """Eq. 3: o_max = max over the offset tensor of |o_i| (sign = direction)."""
    return jnp.max(jnp.abs(offsets))


def is_modulated(channels: int, taps: int) -> bool:
    """Whether an offset-conv output of ``channels`` channels belongs to
    a modulated (DCNv2, Zhu et al. 2019) layer: 3*taps channels, the
    (dy, dx) offsets then one mask logit per tap, against the 2*taps
    offsets of a DCNv1 layer.  Any other count raises."""
    if channels not in (2 * taps, 3 * taps):
        raise ValueError(
            f"an offset-conv output has 2*K*K = {2 * taps} channels "
            f"(DCNv1) or 3*K*K = {3 * taps} (DCNv2: offsets, then mask "
            f"logits); got {channels}")
    return channels == 3 * taps


def split_offset_channels(o: Array, taps: int) -> tuple[Array, Array | None]:
    """(offsets, mask) of an offset-conv output: channels ``[0, 2*taps)``
    are the offsets, and on a modulated layer the mask is the sigmoid of
    the remaining ``taps`` logits, one factor in [0, 1] per tap (None on
    a DCNv1 layer).  The Eq. 4 bound applies to the offsets only."""
    if not is_modulated(o.shape[-1], taps):
        return o, None
    return o[..., :2 * taps], jax.nn.sigmoid(o[..., 2 * taps:])


def receptive_field(kernel_size: int, o_max: float) -> int:
    """Eq. 4: RF = K_C + 2 * ceil(o_max)."""
    return int(kernel_size + 2 * math.ceil(float(o_max)))


def receptive_field_dynamic(kernel_size: int, o_max: Array) -> Array:
    """Traced version of Eq. 4 (used inside jitted stat collection)."""
    return kernel_size + 2 * jnp.ceil(o_max)


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DCLConfig:
    """Static configuration of one deformable convolutional layer."""

    in_channels: int
    out_channels: int
    kernel_size: int = 3
    stride: int = 1
    dilation: int = 1
    # Hardware-friendly receptive-field bound B (None = unbounded, the
    # paper's lambda=0 baseline).  With a bound, RF = K + 2*ceil(B).
    offset_bound: float | None = None
    use_bias: bool = True
    # dtype of the compute (params kept in float32 by default; cast at use).
    dtype: Any = jnp.float32

    @property
    def taps(self) -> int:
        return self.kernel_size * self.kernel_size

    @property
    def pad(self) -> int:
        # SAME-style padding for the regular grid (matches mmdetection DCN).
        return self.dilation * (self.kernel_size // 2)

    def static_rf(self) -> int | None:
        if self.offset_bound is None:
            return None
        return receptive_field(self.kernel_size, self.offset_bound)


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_dcl_params(key: Array, cfg: DCLConfig) -> dict[str, Array]:
    """Init DCL params.

    Offset conv weights are initialised to zero (standard DCN practice:
    the layer starts as a plain convolution), deform weights use He init.
    """
    k_deform, = jax.random.split(key, 1)
    K, C, M = cfg.kernel_size, cfg.in_channels, cfg.out_channels
    fan_in = K * K * C
    w_deform = jax.random.normal(k_deform, (K, K, C, M), jnp.float32)
    w_deform = w_deform * jnp.sqrt(2.0 / fan_in)
    params = {
        "w_offset": jnp.zeros((K, K, C, 2 * K * K), jnp.float32),
        "w_deform": w_deform,
    }
    if cfg.use_bias:
        params["b_offset"] = jnp.zeros((2 * K * K,), jnp.float32)
        params["b_deform"] = jnp.zeros((M,), jnp.float32)
    return params


# ---------------------------------------------------------------------------
# Standard convolution helper (NHWC x HWIO -> NHWC)
# ---------------------------------------------------------------------------

def conv2d(x: Array, w: Array, *, stride: int = 1, dilation: int = 1,
           padding: str | int = "SAME") -> Array:
    if isinstance(padding, int):
        pad = [(padding, padding), (padding, padding)]
    else:
        pad = padding
    return lax.conv_general_dilated(
        x, w,
        window_strides=(stride, stride),
        padding=pad,
        rhs_dilation=(dilation, dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


# ---------------------------------------------------------------------------
# Bilinear sampling (the g(x, o) of Eq. 2)
# ---------------------------------------------------------------------------

def bilinear_sample(x: Array, pos_y: Array, pos_x: Array) -> Array:
    """Bilinearly sample ``x`` at float positions, zero outside the image.

    x:            (N, H, W, C)
    pos_y, pos_x: (N, P) float sample coordinates (pixel units)
    returns:      (N, P, C) in x.dtype

    Positions and interpolation coefficients are computed in fp32
    regardless of the data dtype (hardware analogue: the sampling
    controller generates addresses and coefficients at full precision
    even when the datapath is bf16); corner values accumulate in fp32
    and round once at the end.
    """
    N, H, W, C = x.shape
    pos_y = pos_y.astype(jnp.float32)
    pos_x = pos_x.astype(jnp.float32)
    y0 = jnp.floor(pos_y)
    x0 = jnp.floor(pos_x)
    ty = pos_y - y0  # in [0, 1)
    tx = pos_x - x0
    y0 = y0.astype(jnp.int32)
    x0 = x0.astype(jnp.int32)

    flat = x.reshape(N, H * W, C)

    def corner(yc: Array, xc: Array, wgt: Array) -> Array:
        valid = ((yc >= 0) & (yc < H) & (xc >= 0) & (xc < W))
        idx = jnp.clip(yc, 0, H - 1) * W + jnp.clip(xc, 0, W - 1)
        v = jnp.take_along_axis(flat, idx[..., None], axis=1)
        return v.astype(jnp.float32) \
            * (wgt * valid.astype(jnp.float32))[..., None]

    out = corner(y0, x0, (1.0 - ty) * (1.0 - tx))
    out = out + corner(y0, x0 + 1, (1.0 - ty) * tx)
    out = out + corner(y0 + 1, x0, ty * (1.0 - tx))
    out = out + corner(y0 + 1, x0 + 1, ty * tx)
    return out.astype(x.dtype)


def sample_patches(x: Array, offsets: Array, cfg: DCLConfig) -> Array:
    """g(x, o): gather bilinearly-interpolated K*K patches.

    x:       (N, H, W, C)
    offsets: (N, Ho, Wo, K*K, 2)   ([..., 0]=dy, [..., 1]=dx)
    returns: (N, Ho, Wo, K*K, C) interpolated inputs (the tensor the
             paper's stage 1 writes to its output buffer).
    """
    N, H, W, C = x.shape
    K, S, D, P = cfg.kernel_size, cfg.stride, cfg.dilation, cfg.pad
    Ho = (H + 2 * P - D * (K - 1) - 1) // S + 1
    Wo = (W + 2 * P - D * (K - 1) - 1) // S + 1
    assert offsets.shape == (N, Ho, Wo, K * K, 2), (
        f"offsets {offsets.shape} != {(N, Ho, Wo, K * K, 2)}")

    # Regular-grid base positions for every (output position, tap).
    oy = jnp.arange(Ho) * S - P            # (Ho,)
    ox = jnp.arange(Wo) * S - P            # (Wo,)
    ky, kx = jnp.meshgrid(jnp.arange(K) * D, jnp.arange(K) * D, indexing="ij")
    ky = ky.reshape(-1)                    # (K*K,)
    kx = kx.reshape(-1)

    base_y = oy[:, None, None] + ky[None, None, :]          # (Ho, 1, K*K)
    base_x = ox[None, :, None] + kx[None, None, :]          # (1, Wo, K*K)
    base_y = jnp.broadcast_to(base_y, (Ho, Wo, K * K))
    base_x = jnp.broadcast_to(base_x, (Ho, Wo, K * K))

    pos_y = base_y[None].astype(jnp.float32) + offsets[..., 0].astype(jnp.float32)
    pos_x = base_x[None].astype(jnp.float32) + offsets[..., 1].astype(jnp.float32)

    Pn = Ho * Wo * K * K
    sampled = bilinear_sample(x, pos_y.reshape(N, Pn), pos_x.reshape(N, Pn))
    return sampled.reshape(N, Ho, Wo, K * K, C)


# ---------------------------------------------------------------------------
# Full deformable convolution layer (Eq. 1 + Eq. 2)
# ---------------------------------------------------------------------------

def dcl_forward(params: dict[str, Array], x: Array, cfg: DCLConfig,
                *, return_stats: bool = True):
    """Run one DCL: offset conv -> clamp (optional) -> sample -> conv.

    A ``w_offset`` with 3*K*K outputs makes the layer modulated (DCNv2):
    each sampled tap is multiplied by the sigmoid of its mask logit
    (``split_offset_channels``).

    Returns ``(y, stats)`` where ``stats['o_max']`` is the Eq. 3 statistic
    of the *unclamped* offsets (what the Eq. 5 regularizer penalises) and
    ``stats['offsets']`` the (possibly clamped) offsets actually used.
    """
    N, H, W, C = x.shape
    K = cfg.kernel_size
    xc = x.astype(cfg.dtype)

    # Stage 1a: offset generation (Eq. 1).
    o = conv2d(xc, params["w_offset"].astype(cfg.dtype),
               stride=cfg.stride, dilation=cfg.dilation, padding=cfg.pad)
    if "b_offset" in params:
        o = o + params["b_offset"].astype(cfg.dtype)
    Ho, Wo = o.shape[1], o.shape[2]
    o, mask = split_offset_channels(o, K * K)
    offsets = o.reshape(N, Ho, Wo, K * K, 2)

    o_max = offset_abs_max(offsets)
    if cfg.offset_bound is not None:
        offsets = jnp.clip(offsets, -cfg.offset_bound, cfg.offset_bound)

    # Stage 1b: bilinear sampling (the g of Eq. 2), each tap scaled by
    # its mask on a modulated layer.
    patches = sample_patches(xc, offsets, cfg)  # (N, Ho, Wo, K*K, C)
    if mask is not None:
        patches = patches * mask[..., None].astype(patches.dtype)

    # Stage 2: dynamic convolution == matmul over (K*K, C) taps (MXU-friendly).
    w = params["w_deform"].astype(cfg.dtype).reshape(K * K, C, cfg.out_channels)
    y = jnp.einsum("nhwkc,kcm->nhwm", patches, w,
                   preferred_element_type=jnp.float32).astype(cfg.dtype)
    if "b_deform" in params:
        y = y + params["b_deform"].astype(cfg.dtype)

    if not return_stats:
        return y
    stats = {"o_max": o_max, "rf_dynamic": receptive_field_dynamic(K, o_max)}
    return y, stats


@partial(jax.jit, static_argnames=("cfg",))
def dcl_forward_jit(params, x, cfg: DCLConfig):
    return dcl_forward(params, x, cfg)
