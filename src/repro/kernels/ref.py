"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against
(``interpret=True`` on CPU, sweeping shapes/dtypes — see
``tests/test_kernels.py``).  They intentionally share code with
``repro.core.deform_conv`` — the reference DCL semantics live there.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.deform_conv import (DCLConfig, sample_patches,
                                    split_offset_channels)

Array = jax.Array


def matmul_ref(x: Array, w: Array) -> Array:
    """fp32-accumulated matmul oracle."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def _cfg(c: int, k: int, stride: int, dilation: int) -> DCLConfig:
    return DCLConfig(in_channels=c, out_channels=1, kernel_size=k,
                     stride=stride, dilation=dilation)


def deform_sample_ref(x: Array, offsets: Array, *, kernel_size: int = 3,
                      stride: int = 1, dilation: int = 1,
                      offset_bound: float | None = None) -> Array:
    """Oracle for the stage-1 sampling kernel.

    x:       (N, H, W, C)
    offsets: (N, Ho, Wo, 2*K*K) raw offset-conv output, pairs (dy, dx);
             (N, Ho, Wo, 3*K*K) for a modulated (DCNv2) layer, whose
             last K*K channels are the mask logits
    returns: (N, Ho, Wo, K*K, C) bilinearly interpolated patches.

    Semantics match the kernel: offsets are clamped to ``offset_bound``
    (the trained Eq. 5 bound) before sampling; samples outside the image
    contribute zero; a modulated layer's taps are then multiplied by the
    sigmoid of their mask logits.
    """
    n, _, _, c = x.shape
    k2 = kernel_size * kernel_size
    ho, wo = offsets.shape[1], offsets.shape[2]
    offsets, mask = split_offset_channels(offsets, k2)
    off = offsets.reshape(n, ho, wo, k2, 2)
    if offset_bound is not None:
        off = jnp.clip(off, -offset_bound, offset_bound)
    patches = sample_patches(x, off, _cfg(c, kernel_size, stride, dilation))
    if mask is None:
        return patches
    return patches * mask[..., None].astype(patches.dtype)


def flash_attention_ref(q: Array, k: Array, v: Array, *, causal: bool = True,
                        softcap: float | None = None) -> Array:
    """Dense oracle for the flash-attention kernel (GQA layout).

    q: (B, Sq, KV, G, Dh); k, v: (B, Sk, KV, Dh) -> (B, Sq, KV, G, Dh).
    """
    import math
    b, sq, kv, g, dh = q.shape
    sk = k.shape[1]
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(dh)
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    if causal:
        mask = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def deform_conv_fused_ref(x: Array, offsets: Array, w: Array, *,
                          kernel_size: int = 3, stride: int = 1,
                          dilation: int = 1,
                          offset_bound: float | None = None) -> Array:
    """Oracle for the fused sampling + dynamic-convolution kernel.

    w: (K*K, C, M) deform weights.  Returns (N, Ho, Wo, M).
    """
    patches = deform_sample_ref(
        x, offsets, kernel_size=kernel_size, stride=stride,
        dilation=dilation, offset_bound=offset_bound)
    y = jnp.einsum("nhwkc,kcm->nhwm", patches, w,
                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype)
