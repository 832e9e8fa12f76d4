"""int8 zero-copy fused DCL kernels (quantized datapath + layer chaining).

The paper's accelerator computes in fixed point; the TPU analogue is an
int8 band dataflow: the Eq. 6 geometry is dtype-independent, but at
1 byte/elem every VMEM byte holds 4x more of the offset band than fp32,
so the Sec. 3.2 chooser (``tiling.choose_kernel_tiles(dtype="int8")``)
runs wider tiles at the same budget and the modeled HBM input traffic
drops ~4x (gated >= 3x in ``tests/test_quant.py``).

Precision split (CoDeNet / Xu et al. 2021 — deformable conv tolerates
8-bit weights/activations when interpolation stays high precision):

* the **band DMA** streams symmetric-int8 activations HBM -> VMEM
  through the same double-buffered pipeline as the fp32 kernel
  (``band_pipeline.BandStager`` — one geometry, two dtypes);
* **bilinear coefficients are fp32**: the shared shifted-window
  sampler (``band_pipeline.sample_row_taps``) forms corner positions
  and fractions in fp32 (address generation is always full precision),
  the int8 corner values combine in fp32, and the result is re-rounded
  onto the activation grid.  A bilinear mix is
  convex, so the combination of in-range int8 values is in range —
  requantization is a pure round, never a clip, and the patch scale is
  exactly the activation scale;
* the **MXU contraction runs int8 x int8 -> int32** (exact
  accumulation, no fp32 rounding inside the reduction);
* the epilogue is plan-selected: a **fused dequant**
  (``deform_conv_fused_zerocopy_q`` — rescale by the per-output-channel
  ``s_x * s_w[m]``, emit fp32) or a **fused requant**
  (``deform_conv_fused_zerocopy_chain`` — rescale by
  ``s_x * s_w[m] / s_y`` with the bias folded as ``b[m] / s_y``, round,
  clip, emit int8 on the next layer's activation grid).  Either way the
  quantized tensor never round-trips HBM at fp32.

The chain kernel additionally fuses the **offset-conv stage**
(``band_pipeline.offset_conv_row``): the offset conv's undeformed
taps are a static-index subset of the staged Eq. 6 band, so the raw
offsets are produced in-kernel from the int8 band + quantized offset
weights — no separate fp32 offset pass and no offsets in HBM at all.

Both kernels are emitted by ``band_pipeline.forward_call``; this module
only builds their ``DCLPlan``s.  Quantization/padding commute because
the grid is symmetric (0 -> 0), so the padded int8 plane needs no
special casing.  These are *inference* datapaths; training uses the
fake-quant QAT/chain wrappers of ``repro.quant.qat`` (STE) through the
fp32 custom-VJP kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.deform_conv import is_modulated
from .band_pipeline import BandSpec, DCLPlan, forward_call

Array = jax.Array


def _int8_plan(*, kernel_size: int, stride: int, dilation: int,
               offset_bound: float, tile_h: int, tile_w: int, tile_c: int,
               tile_m: int, epilogue: str, fuse_offsets: bool,
               offset_channels: int) -> DCLPlan:
    return DCLPlan(
        band=BandSpec(kernel_size=kernel_size, stride=stride,
                      dilation=dilation, offset_bound=offset_bound,
                      tile_h=tile_h, tile_w=tile_w),
        tile_c=tile_c, tile_m=tile_m, band_dtype="int8", acc_dtype="int32",
        epilogue=epilogue, fuse_offsets=fuse_offsets,
        modulated=is_modulated(offset_channels, kernel_size ** 2))


@functools.partial(
    jax.jit,
    static_argnames=("kernel_size", "stride", "dilation", "offset_bound",
                     "tile_h", "tile_w", "tile_c", "tile_m", "interpret"))
def deform_conv_fused_zerocopy_q(x_pad_q: Array, offsets: Array,
                                 w_tiles_q: Array, scale: Array, *,
                                 kernel_size: int, stride: int,
                                 dilation: int, offset_bound: float,
                                 tile_h: int, tile_w: int,
                                 tile_c: int | None = None,
                                 tile_m: int | None = None,
                                 interpret: bool = True) -> Array:
    """int8 fused DCL over the whole padded input (zero-copy dataflow).

    x_pad_q:   (N, Hp, Wp, C) int8 zero-padded input, whole in ANY/HBM
    offsets:   (N, Ho, Wo, 2*K*K) fp32 raw offsets (full precision);
               3*K*K channels with a modulated layer's mask logits
    w_tiles_q: (C//tile_c, K*K*tile_c, M) int8 ``plan.tile_weights`` layout
    scale:     (1, M) fp32 combined dequant scale ``s_x * s_w[m]``
    returns:   (N, Ho, Wo, M) fp32 (dequantized by the fused epilogue)
    """
    assert x_pad_q.dtype == jnp.int8, x_pad_q.dtype
    assert w_tiles_q.dtype == jnp.int8, w_tiles_q.dtype
    c = x_pad_q.shape[-1]
    m = w_tiles_q.shape[2]
    plan = _int8_plan(kernel_size=kernel_size, stride=stride,
                      dilation=dilation, offset_bound=offset_bound,
                      tile_h=tile_h, tile_w=tile_w, tile_c=tile_c or c,
                      tile_m=tile_m or m, epilogue="dequant",
                      fuse_offsets=False, offset_channels=offsets.shape[-1])
    return forward_call(plan, x_pad_q, offsets, w_tiles_q, scale=scale,
                        out_dtype=jnp.float32, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("kernel_size", "stride", "dilation", "offset_bound",
                     "tile_h", "tile_w", "tile_m", "emit", "ho", "wo",
                     "interpret"))
def deform_conv_fused_zerocopy_chain(x_pad_q: Array, w_tiles_q: Array,
                                     woff_tiles_q: Array, off_scale: Array,
                                     off_bias: Array, out_scale: Array,
                                     out_bias: Array, *, kernel_size: int,
                                     stride: int, dilation: int,
                                     offset_bound: float, tile_h: int,
                                     tile_w: int, tile_m: int | None = None,
                                     emit: str = "int8", ho: int, wo: int,
                                     interpret: bool = True) -> Array:
    """Chained int8 DCL: fused offset-conv stage + int8 output emission.

    x_pad_q:      (N, Hp, Wp, C) int8 zero-padded input (the previous
                  chained layer's emission, or the chain head quantized
                  once) — the whole C extent is staged per band
                  (``tile_c = C``, required by the fused offset stage)
    w_tiles_q:    (1, K*K*C, M) int8 deform weights
    woff_tiles_q: (1, K*K*C, 2*K*K) int8 offset-conv weights; 3*K*K
                  outputs (offsets, then mask logits) for a modulated
                  (DCNv2) layer
    off_scale:    (1, 2*K*K or 3*K*K) fp32 ``s_x * s_woff`` dequant scales
    off_bias:     (1, 2*K*K or 3*K*K) fp32 offset-conv bias
    out_scale:    (1, M) fp32 — ``s_x * s_w[m] / s_y`` (``emit="int8"``,
                  the per-channel requant onto the next layer's grid) or
                  ``s_x * s_w[m]`` (``emit="fp32"``, the chain tail)
    out_bias:     (1, M) fp32 — ``b[m] / s_y`` resp. ``b[m]``
    returns:      (N, ho, wo, M) int8 on the ``s_y`` grid, or fp32
    """
    assert x_pad_q.dtype == jnp.int8, x_pad_q.dtype
    assert w_tiles_q.dtype == jnp.int8, w_tiles_q.dtype
    assert woff_tiles_q.dtype == jnp.int8, woff_tiles_q.dtype
    if emit not in ("int8", "fp32"):
        raise ValueError(f"unknown emit {emit!r}; expected 'int8' or 'fp32'")
    c = x_pad_q.shape[-1]
    m = w_tiles_q.shape[2]
    plan = _int8_plan(kernel_size=kernel_size, stride=stride,
                      dilation=dilation, offset_bound=offset_bound,
                      tile_h=tile_h, tile_w=tile_w, tile_c=c,
                      tile_m=tile_m or m,
                      epilogue="requant" if emit == "int8" else "dequant",
                      fuse_offsets=True,
                      offset_channels=woff_tiles_q.shape[-1])
    return forward_call(plan, x_pad_q, None, w_tiles_q, scale=out_scale,
                        bias=out_bias, woff_tiles=woff_tiles_q,
                        off_scale=off_scale, off_bias=off_bias, ho=ho, wo=wo,
                        out_dtype=jnp.int8 if emit == "int8" else jnp.float32,
                        interpret=interpret)
