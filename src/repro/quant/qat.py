"""Quantization-aware training wrappers for the DCL.

QAT simulates the int8 datapath of ``kernels/deform_conv_q.py`` inside
the fp32 training graph: the deform-conv input plane and the deform
weights are fake-quantized (``qtypes.fake_quant``, STE backward) before
the convolution, while the offset-generating conv, the bilinear
coefficients, and every gradient stay fp32 — exactly the precision
split of the int8 kernel.  Because fake-quant is applied *outside*
``ops.deform_conv``, the Trainer's backward still routes through the
existing custom-VJP zero-copy kernel path untouched: STE passes the
cotangent through the quantization grid, then the fused backward kernel
does the rest.

``fake_quant_dcl_reference`` is the bit-level oracle of the int8
kernel: quantize input per-tensor and weights per-channel, sample with
fp32 bilinear coefficients, re-round the patches onto the activation
grid (the convex bilinear combination of int8 values stays in range, so
re-rounding loses no range), contract, and rescale.  Tests gate the
kernel against it to <= 1 LSB of the per-channel output scale
(``s_x * s_w[m]``).
"""
from __future__ import annotations

from typing import Any, Mapping

import jax
import jax.numpy as jnp

from .qtypes import QMAX, compute_scale, fake_quant, fake_quant_absmax

Array = jax.Array


def qat_quantize_inputs(x: Array, w: Array, *,
                        x_scale: Array | None = None,
                        w_scale: Array | None = None
                        ) -> tuple[Array, Array]:
    """Fake-quantize one DCL's (input plane, deform weights) pair.

    x: (N, H, W, C) activation, per-tensor scale; w: (..., M) weights,
    per-output-channel scales.  Scales default to dynamic absmax
    (stop-gradient observers); calibrated values override.
    """
    if x_scale is None:
        xq = fake_quant_absmax(x)
    else:
        xq = fake_quant(x, jnp.asarray(x_scale, jnp.float32))
    if w_scale is None:
        wq = fake_quant_absmax(w, axis=-1)
    else:
        s = jnp.asarray(w_scale, jnp.float32)
        if s.ndim == 1:
            s = s.reshape((1,) * (w.ndim - 1) + (-1,))
        wq = fake_quant(w, s)
    return xq, wq


def qat_dcl_apply(params: Mapping[str, Array], x: Array, *,
                  scales: Mapping[str, Any] | None = None,
                  **dcl_kwargs):
    """Fake-quant wrapper around ``models.layers.dcl_apply``.

    Quantizes the deform-conv operands (activation per-tensor, weights
    per-channel) and delegates to the unmodified layer — both the
    pure-JAX reference and the Pallas kernel path (``use_kernel=True``)
    see the fake-quantized values, so QAT trains end-to-end through the
    custom-VJP zero-copy kernels.  ``scales`` is one calibration-table
    entry ({"x_scale": float, "w_scale": [...]}); None = dynamic absmax.
    """
    from repro.models.layers import dcl_apply

    x_scale = scales.get("x_scale") if scales else None
    w_scale = scales.get("w_scale") if scales else None
    xq, wq = qat_quantize_inputs(x, params["w_deform"],
                                 x_scale=x_scale, w_scale=w_scale)
    qparams = dict(params)
    qparams["w_deform"] = wq
    return dcl_apply(qparams, xq, **dcl_kwargs)


def fake_quant_dcl_chain_reference(x: Array, w: Array, w_offset: Array,
                                   b_offset: Array,
                                   b_deform: Array | None = None, *,
                                   kernel_size: int = 3, stride: int = 1,
                                   dilation: int = 1,
                                   offset_bound: float | None = None,
                                   x_scale=None, w_scale=None,
                                   w_offset_scale=None, y_scale=None
                                   ) -> tuple[Array, Array]:
    """STE fake-quant oracle of the chained int8 kernel — the *training*
    path of ``quant="int8_chain"`` (differentiable end-to-end).

    Simulates every quantization boundary of
    ``kernels.deform_conv_q.deform_conv_fused_zerocopy_chain`` on the
    fp32 graph with straight-through estimators:

    * input and weights fake-quantize onto their calibrated grids
      (activation per-tensor, deform/offset weights per-out-channel);
    * the offsets come from the *quantized* offset conv (the fused
      in-kernel stage) plus the fp32 bias — address generation itself
      stays fp32, matching the kernel's dequant;
    * the sampled patches re-round onto the activation grid (the
      kernel's patch requantization before its MXU step);
    * the output (bias folded first — int8 emission quantizes ``y + b``)
      fake-quantizes onto the next layer's ``y_scale`` grid when given
      (the per-channel requant epilogue; ``y_scale=None`` is the fp32
      chain tail).

    A modulated (DCNv2) layer's ``w_offset`` has 3*K*K outputs and its
    taps are masked before the patch round (``deform_sample_ref``).

    Returns ``(y, offsets)`` — offsets (and a modulated layer's mask
    logits after them) feed the Eq. 5 ``o_max``
    statistic, which the fused kernel never materializes.  Values agree
    with the kernel to <= 1 LSB of the emission grid (fp32 product
    rounding in the offset conv vs the kernel's exact int32
    accumulation; see ``tests/test_chain.py``).
    """
    from repro.core.deform_conv import conv2d
    from repro.kernels.ref import deform_sample_ref

    k = kernel_size
    cin = x.shape[-1]
    cout = w.shape[-1]
    if x_scale is None:
        raise ValueError(
            "the chain reference needs x_scale: chained layers exchange "
            "values on a pinned activation grid (calibrate first)")
    sx = jnp.asarray(x_scale, jnp.float32)
    xq = fake_quant(x, sx)
    if w_scale is None:
        wq = fake_quant_absmax(w, axis=-1)
    else:
        wq = fake_quant(w, jnp.asarray(w_scale, jnp.float32)
                        .reshape(1, 1, cout))
    n_off = w_offset.shape[-1]          # 2*K*K, or 3*K*K modulated
    if w_offset_scale is None:
        woq = fake_quant_absmax(w_offset, axis=-1)
    else:
        woq = fake_quant(w_offset, jnp.asarray(w_offset_scale, jnp.float32)
                         .reshape(1, 1, n_off))
    offsets = conv2d(xq, woq.reshape(k, k, cin, n_off), stride=stride,
                     dilation=dilation, padding=dilation * (k // 2))
    offsets = offsets + jnp.asarray(b_offset, jnp.float32)
    patches = deform_sample_ref(
        xq, offsets, kernel_size=k, stride=stride, dilation=dilation,
        offset_bound=offset_bound)
    patches_q = fake_quant(patches, sx)
    y = jnp.einsum("nhwkc,kcm->nhwm", patches_q, wq,
                   preferred_element_type=jnp.float32)
    if b_deform is not None:
        y = y + jnp.asarray(b_deform, jnp.float32)
    if y_scale is not None:
        y = fake_quant(y, jnp.asarray(y_scale, jnp.float32))
    return y.astype(x.dtype), offsets


def fake_quant_dcl_reference(x: Array, offsets: Array, w: Array, *,
                             kernel_size: int = 3, stride: int = 1,
                             dilation: int = 1,
                             offset_bound: float | None = None,
                             x_scale: Array | None = None,
                             w_scale: Array | None = None) -> Array:
    """Pure-XLA fake-quant oracle of the int8 zero-copy kernel.

    Mirrors the kernel's arithmetic step for step in fp32 holding
    integer values: the int32 MXU accumulation is exact, and for the
    magnitudes involved (|q| <= 127, K^2*C terms) so is this fp32
    einsum — the only divergence is fp32 coefficient rounding in the
    bilinear stage, bounded well under 1 output LSB.
    """
    from repro.kernels.ref import deform_sample_ref

    sx = compute_scale(x) if x_scale is None \
        else jnp.asarray(x_scale, jnp.float32)
    sw = compute_scale(w, axis=-1) if w_scale is None \
        else jnp.asarray(w_scale, jnp.float32).reshape(1, 1, -1)
    xq = jnp.clip(jnp.round(x.astype(jnp.float32) / sx), -QMAX, QMAX)
    wq = jnp.clip(jnp.round(w.astype(jnp.float32) / sw), -QMAX, QMAX)
    # Sample the integer-valued plane with fp32 coefficients, then
    # re-round the patches onto the activation grid (what the kernel's
    # int8 requantization before the MXU does).
    patches = deform_sample_ref(
        xq, offsets, kernel_size=kernel_size, stride=stride,
        dilation=dilation, offset_bound=offset_bound)
    patches_q = jnp.round(patches)
    y = jnp.einsum("nhwkc,kcm->nhwm", patches_q, wq,
                   preferred_element_type=jnp.float32)
    return (y * sx * sw.reshape(1, 1, 1, -1)).astype(x.dtype)
