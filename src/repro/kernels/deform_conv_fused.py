"""Fused Pallas kernel: bilinear sampling + dynamic convolution (stage 1+2).

The paper's accelerator pipelines two stages *through DRAM*: interpolated
patches are written to the output buffer, transferred to DRAM, and
re-fetched as inputs of the dynamic-convolution stage (their Fig. 4).
On TPU we can do better: the sampled (T_H*T_W, K^2*T_C) patch tile is
exactly an im2col operand for the MXU, so this kernel samples into VMEM
registers and immediately feeds the MXU — the patches never exist in
HBM.  Per Eq. 7, that removes 2*K^2*T_W*T_N elements/tile of round-trip
traffic (the dominant HBM term for small N; see EXPERIMENTS.md §Perf).

The zero-copy kernel is emitted by ``band_pipeline.forward_call`` from a
fp32 ``DCLPlan`` (grid ``(n, h_tiles, w_tiles, m_tiles, c_steps)``,
double-buffered band stager, fp32 MXU accumulation, plain-cast flush) —
the same emitter that instantiates the int8 and chained variants
(``deform_conv_q``) and whose band staging the backward kernel shares.

``deform_conv_fused_banded`` (legacy) consumes the HBM-materialized
overlapping bands of ``kernels.plan.pad_and_band`` (a
``band_h/(tile_h*stride)``-fold duplication of the input written and
re-read through HBM) and stages full-width bands per block via the
BlockSpec pipeline.  Kept as the parity/regression baseline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.deform_conv import is_modulated
from .band_pipeline import (BandSpec, DCLPlan, compiler_params,
                            for_each_row, forward_call, sample_row_taps)

Array = jax.Array


@functools.partial(
    jax.jit,
    static_argnames=("kernel_size", "stride", "dilation", "offset_bound",
                     "tile_h", "tile_w", "tile_c", "tile_m", "interpret"))
def deform_conv_fused_zerocopy(x_pad: Array, offsets: Array,
                               w_tiles: Array, *, kernel_size: int,
                               stride: int, dilation: int,
                               offset_bound: float, tile_h: int,
                               tile_w: int, tile_c: int | None = None,
                               tile_m: int | None = None,
                               interpret: bool = True) -> Array:
    """Fused DCL over the whole padded input (no band materialization).

    x_pad:   (N, Hp, Wp, C) zero-padded input, left whole in ANY/HBM
    offsets: (N, Ho, Wo, 2*K*K), Ho = h_tiles*tile_h, Wo = w_tiles*tile_w;
             (N, Ho, Wo, 3*K*K) with the mask logits of a modulated
             (DCNv2) layer
    w_tiles: (C//tile_c, K*K*tile_c, M) — deform weights pre-tiled by
             ``plan.tile_weights`` so each C-step reads one contiguous block.
    returns: (N, Ho, Wo, M)
    """
    c = x_pad.shape[-1]
    m = w_tiles.shape[2]
    plan = DCLPlan(
        band=BandSpec(kernel_size=kernel_size, stride=stride,
                      dilation=dilation, offset_bound=offset_bound,
                      tile_h=tile_h, tile_w=tile_w),
        tile_c=tile_c or c, tile_m=tile_m or m, epilogue="cast",
        band_dtype=x_pad.dtype.name,
        modulated=is_modulated(offsets.shape[-1], kernel_size ** 2))
    return forward_call(plan, x_pad, offsets, w_tiles,
                        out_dtype=x_pad.dtype, interpret=interpret)


# ---------------------------------------------------------------------------
# Legacy banded dataflow (HBM-materialized bands) — parity baseline
# ---------------------------------------------------------------------------

def _fused_kernel(bands_ref, off_ref, w_ref, out_ref, patch_ref, acc_ref,
                  *, spec: BandSpec, c_steps: int):
    cc = pl.program_id(3)

    @pl.when(cc == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _row(t):
        taps = sample_row_taps([bands_ref.at[0, 0]], off_ref.at[0], t, spec)
        patch_ref[t] = jnp.concatenate(taps, axis=-1)
    for_each_row(spec, _row)
    # (tile_h*wo, k2*tc) @ (k2*tc, tm) on the MXU, fp32 accumulation.
    lhs = patch_ref[...].reshape(spec.tile_h * spec.tile_w, -1)
    acc_ref[...] += jnp.dot(lhs, w_ref[0],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(cc == c_steps - 1)
    def _flush():
        tm = out_ref.shape[-1]
        out_ref[0] = acc_ref[...].reshape(
            spec.tile_h, spec.tile_w, tm).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("kernel_size", "stride", "dilation", "offset_bound",
                     "tile_h", "tile_c", "tile_m", "interpret"))
def deform_conv_fused_banded(bands: Array, offsets: Array, w_tiles: Array, *,
                             kernel_size: int, stride: int, dilation: int,
                             offset_bound: float, tile_h: int,
                             tile_c: int | None = None,
                             tile_m: int | None = None,
                             interpret: bool = True) -> Array:
    """Fused DCL over pre-banded input.

    bands:   (N, n_tiles, band_h, w_pad, C)
    offsets: (N, Ho, Wo, 2*K*K)
    w_tiles: (C//tile_c, K*K*tile_c, M) — deform weights pre-tiled by
             ``plan.tile_weights`` so each C-step reads one contiguous block.
    returns: (N, Ho, Wo, M)
    """
    n, n_tiles, band_h, w_pad, c = bands.shape
    _, ho, wo, _ = offsets.shape
    k2 = kernel_size * kernel_size
    tc = tile_c or c
    assert c % tc == 0
    c_steps = c // tc
    assert w_tiles.shape[0] == c_steps and w_tiles.shape[1] == k2 * tc
    m = w_tiles.shape[2]
    tm = tile_m or m
    assert m % tm == 0

    return pl.pallas_call(
        functools.partial(
            _fused_kernel,
            spec=BandSpec(kernel_size=kernel_size, stride=stride,
                          dilation=dilation, offset_bound=offset_bound,
                          tile_h=tile_h, tile_w=wo),
            c_steps=c_steps),
        grid=(n, n_tiles, m // tm, c_steps),
        in_specs=[
            pl.BlockSpec((1, 1, band_h, w_pad, tc),
                         lambda i, j, mm, cc: (i, j, 0, 0, cc)),
            pl.BlockSpec((1, tile_h, wo, 2 * k2),
                         lambda i, j, mm, cc: (i, j, 0, 0)),
            pl.BlockSpec((1, k2 * tc, tm),
                         lambda i, j, mm, cc: (cc, 0, mm)),
        ],
        out_specs=pl.BlockSpec((1, tile_h, wo, tm),
                               lambda i, j, mm, cc: (i, j, 0, mm)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, m), bands.dtype),
        scratch_shapes=[pltpu.VMEM((tile_h, wo, k2 * tc), jnp.float32),
                        pltpu.VMEM((tile_h * wo, tm), jnp.float32)],
        compiler_params=compiler_params(("parallel", "parallel",
                                         "parallel", "arbitrary")),
        interpret=interpret,
    )(bands, offsets, w_tiles)
