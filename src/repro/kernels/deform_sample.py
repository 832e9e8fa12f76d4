"""Stage-1 Pallas kernel: bounded-halo bilinear sampling (paper Fig. 4).

This is the paper's *input sampling stage* adapted to the TPU memory
hierarchy.  The whole point of the Eq. 5 regularizer is that the trained
offset bound ``B`` makes the receptive field static:

    RF = K + 2*ceil(B)                       (Eq. 4)

so every bilinear sample of an output (row, col) tile provably lies
inside a fixed input band of extent

    BAND = (T - 1)*S + (K - 1)*D + 2*(ceil(B) + 1)    (Eq. 6, per axis)

All gather irregularity is confined to VMEM, where random access costs
nothing compared to HBM.  There is no miss path — offsets are clamped to
``B`` in-kernel (the TPU-idiomatic equivalent of the paper's "provably
no cache miss"), and the input is zero-pre-padded so no validity masks
are needed inside the kernel either: bounded offsets mean every corner
index is in-band by construction.

The zero-copy kernel itself is emitted by ``band_pipeline.forward_call``
from a contraction-free ``DCLPlan`` (``tile_m=None``): the shared
double-buffered band stager + the shifted-window bilinear sampler, with
the patches as the output.  The geometry helper ``band_geometry`` lives
in ``band_pipeline`` and is re-exported here.

``deform_sample_banded`` (legacy) consumes the HBM-materialized
overlapping bands of ``kernels.plan.pad_and_band`` through a BlockSpec
pipeline — kept as the parity/regression baseline (no in-kernel DMA, so
it does not go through the band stager; it samples with the same
``band_pipeline.sample_row_taps``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.deform_conv import is_modulated
from .band_pipeline import (  # noqa: F401  (re-export)
    BandSpec, DCLPlan, band_geometry, compiler_params, for_each_row,
    forward_call, sample_row_taps)

Array = jax.Array


@functools.partial(
    jax.jit,
    static_argnames=("kernel_size", "stride", "dilation", "offset_bound",
                     "tile_h", "tile_w", "tile_c", "interpret"))
def deform_sample_zerocopy(x_pad: Array, offsets: Array, *, kernel_size: int,
                           stride: int, dilation: int, offset_bound: float,
                           tile_h: int, tile_w: int,
                           tile_c: int | None = None,
                           interpret: bool = True) -> Array:
    """Run the zero-copy sampling kernel over the whole padded input.

    x_pad:   (N, Hp, Wp, C) zero-padded input, left whole in ANY/HBM
    offsets: (N, Ho, Wo, 2*K*K), Ho = h_tiles*tile_h, Wo = w_tiles*tile_w;
             (N, Ho, Wo, 3*K*K) with the mask logits of a modulated
             (DCNv2) layer, whose patches come out masked
    returns: (N, Ho, Wo, K*K, C) patches
    """
    c = x_pad.shape[-1]
    plan = DCLPlan(
        band=BandSpec(kernel_size=kernel_size, stride=stride,
                      dilation=dilation, offset_bound=offset_bound,
                      tile_h=tile_h, tile_w=tile_w),
        tile_c=tile_c or c, tile_m=None, band_dtype=x_pad.dtype.name,
        modulated=is_modulated(offsets.shape[-1], kernel_size ** 2))
    return forward_call(plan, x_pad, offsets, interpret=interpret)


# ---------------------------------------------------------------------------
# Legacy banded dataflow (HBM-materialized bands) — parity baseline
# ---------------------------------------------------------------------------

def _sample_kernel(bands_ref, off_ref, out_ref, *, spec: BandSpec):
    def _row(t):
        taps = sample_row_taps([bands_ref.at[0, 0]], off_ref.at[0], t, spec)
        out_ref[0, t] = jnp.stack(taps, axis=1).astype(out_ref.dtype)
    for_each_row(spec, _row)


@functools.partial(
    jax.jit,
    static_argnames=("kernel_size", "stride", "dilation", "offset_bound",
                     "tile_h", "tile_c", "interpret"))
def deform_sample_banded(bands: Array, offsets: Array, *, kernel_size: int,
                         stride: int, dilation: int, offset_bound: float,
                         tile_h: int, tile_c: int | None = None,
                         interpret: bool = True) -> Array:
    """Run the sampling kernel over pre-banded input.

    bands:   (N, n_tiles, band_h, w_pad, C) zero-padded input bands
    offsets: (N, Ho, Wo, 2*K*K) raw offset-conv output (Ho = n_tiles*tile_h)
    returns: (N, Ho, Wo, K*K, C) patches
    """
    n, n_tiles, band_h, w_pad, c = bands.shape
    _, ho, wo, _ = offsets.shape
    assert ho == n_tiles * tile_h, (ho, n_tiles, tile_h)
    k2 = kernel_size * kernel_size
    tc = tile_c or c
    assert c % tc == 0

    return pl.pallas_call(
        functools.partial(
            _sample_kernel,
            spec=BandSpec(kernel_size=kernel_size, stride=stride,
                          dilation=dilation, offset_bound=offset_bound,
                          tile_h=tile_h, tile_w=wo)),
        grid=(n, n_tiles, c // tc),
        in_specs=[
            pl.BlockSpec((1, 1, band_h, w_pad, tc),
                         lambda i, j, cc: (i, j, 0, 0, cc)),
            pl.BlockSpec((1, tile_h, wo, 2 * k2),
                         lambda i, j, cc: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_h, wo, k2, tc),
                               lambda i, j, cc: (i, j, 0, 0, cc)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, k2, c), bands.dtype),
        compiler_params=compiler_params(("parallel", "parallel",
                                         "parallel")),
        interpret=interpret,
    )(bands, offsets)
