"""Fused backward Pallas kernel for the bounded deformable conv.

Training previously differentiated through the pure-XLA gather reference
(``core.deform_conv.dcl_forward``) — exactly the "irregular DRAM access"
regime the paper (arXiv:2006.05238) is designed to avoid, and backward
is *worse* than forward: the gather transposes into an irregular HBM
scatter.  The Eq. 6 band geometry fixes both directions at once: the
same offset bound ``B`` that makes forward gathers provably in-band
makes backward scatters provably in-band, so all irregularity stays in
VMEM.

One fused kernel produces all three cotangents per (batch, row-tile,
width-tile, C-chunk) grid step, re-using a single Eq. 6 band DMA — the
same ``band_pipeline.BandStager`` double-buffer pipeline the forward
kernels are emitted with (the stager's warmup/prefetch/wait are called
individually here so the d_input read-modify-write DMA can be
interleaved into the overlap window):

* the input band chunk streams HBM -> VMEM through the shared stager,
  and the sampled patches are **recomputed** from it (cheap-recompute
  wins the traffic model: saving the (N, Ho, Wo, K^2, C) patch tensor
  as a residual would re-read ``K^2`` times the input volume from HBM,
  vs one extra band read here — see ``tiling.dcl_backward_hbm_bytes``);
* ``d_patches = g @ W^T`` and ``d_weights += patches^T @ g`` run on the
  MXU with fp32 accumulation (``d_weights`` accumulates in a VMEM
  scratch across the whole batch/spatial grid and is emitted fp32);
* ``d_offsets`` reuses the forward's bilinear corner values: for corner
  values v00/v01/v10/v11 at fractions (ty, tx),

      d val / d pos_y = (1-tx)(v10-v00) + tx(v11-v01)
      d val / d pos_x = (1-ty)(v01-v00) + ty(v11-v10)

  contracted against ``d_patches`` over channels, then masked by the
  Eq. 5 clamp (gradient is zero where |raw offset| > B, matching the
  ``jnp.clip`` VJP of the XLA reference almost everywhere);
* ``d_input`` is scattered into a zero VMEM band, then flushed to the
  padded-gradient HBM buffer with an async-copy read-modify-write of
  that band region.  Adjacent tiles overlap only in their Eq. 6 halos,
  and the spatial grid axes are sequential (``arbitrary`` dimension
  semantics), so each flush accumulates into a region no concurrent
  step touches.  The HBM buffer is zero-initialized via
  ``input_output_aliases`` and un-padded by the caller.

The in-kernel scatter uses value-level ``.at[].add`` (duplicate corner
indices accumulate); on a real TPU backend Mosaic lowers small-range
scatters like these via one-hot matmul / sorted segments — the band
extent is the Eq. 6 bound, so the one-hot operand is VMEM-bounded
independent of image size.

**Megacore core split (PR 4).**  The grid is
``(cores, n_per_core, h_tiles, w_tiles, c_steps)`` with the leading
core axis carrying ``parallel`` dimension semantics: each core owns a
disjoint contiguous shard of the batch, so on a Megacore TPU the two
TensorCores split the batch halves instead of serializing the whole
grid.  What makes that safe:

* ``d_input``/``d_offsets`` are indexed by the batch sample — shards
  never read-modify-write the same HBM region, halo rows included
  (halos overlap only *within* a sample's spatial tiles, which stay
  sequential per core);
* ``d_weights`` accumulates in per-core VMEM scratch (hardware gives
  each core a private scratch instance; interpret mode runs core
  subgrids back-to-back, so the per-core init at the first shard step
  gives the same isolation) and flushes one *partial* block per core
  on the core's last spatial step — the caller reduces the
  ``(cores, ...)`` partials with a cheap ``sum`` epilogue.

``cores=1`` reproduces the PR-2/3 sequential kernel bit-for-bit (the
singleton-axis reduce is exact); ``cores>1`` changes only the fp32
summation order of ``d_weights`` (per-core partial sums instead of one
interleaved fold).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .band_pipeline import (N_BUFFERS, BandSpec, DCLPlan, compiler_params,
                            corner_geometry)

Array = jax.Array


def _bwd_zerocopy_kernel(plan: DCLPlan, dx0_hbm, x_hbm, off_ref, g_ref,
                         w_ref, dx_hbm, doff_ref, dw_ref,
                         band_ref, rmw_ref, dw_acc, doff_acc,
                         sem_ref, rmw_sem, *, n_per_core: int,
                         dw_flush_every_step: bool):
    del dx0_hbm  # aliased with dx_hbm (zero-initialized output)
    b_ = plan.band
    k2 = b_.k2
    tile_h, tile_w = b_.tile_h, b_.tile_w
    band_h, band_w = b_.band_h, b_.band_w
    tile_c = plan.tile_c
    core = pl.program_id(0)
    b = pl.program_id(1)
    j = pl.program_id(2)
    ww = pl.program_id(3)
    cc = pl.program_id(4)
    c_steps = pl.num_programs(4)
    i = core * n_per_core + b        # batch sample this grid step owns
    row0 = j * (tile_h * b_.stride)
    col0 = ww * (tile_w * b_.stride)

    stager = plan.stager(x_hbm, band_ref, sem_ref, batch=i, row0=row0,
                         col0=col0)

    def rmw_dma(write: bool):
        region = dx_hbm.at[i, pl.ds(row0, band_h), pl.ds(col0, band_w),
                           pl.ds(cc * tile_c, tile_c)]
        if write:
            return pltpu.make_async_copy(rmw_ref, region, rmw_sem.at[1])
        return pltpu.make_async_copy(region, rmw_ref, rmw_sem.at[0])

    @pl.when(cc == 0)
    def _init_tile():
        doff_acc[...] = jnp.zeros_like(doff_acc)
        stager.warmup()

    # First step of THIS core's batch shard: zero the per-core d_weights
    # accumulator.  The condition is core-local (b, not i) so every
    # core starts its partial sum from zero.
    @pl.when((b == 0) & (j == 0) & (ww == 0))
    def _init_dw():
        dw_acc[cc] = jnp.zeros_like(dw_acc[cc])

    # Start the dx read-modify-write *read* early: it rides under the
    # patch recompute + MXU work below.  The previous grid step's write
    # of any overlapping halo has already completed (sequential spatial
    # grid + the write wait at the end of each step).
    rmw_dma(write=False).start()

    stager.prefetch(cc, c_steps)
    band = stager.wait(cc)

    off_raw = off_ref[0].reshape(tile_h, tile_w, k2, 2)
    y0, x0, ty, tx = corner_geometry(
        off_raw, kernel_size=b_.kernel_size, stride=b_.stride,
        dilation=b_.dilation, offset_bound=b_.offset_bound, tile_h=tile_h,
        wo=tile_w)

    flat = band.reshape(band_h * band_w, tile_c)
    p = tile_h * tile_w * k2
    idx00 = (y0 * band_w + x0).reshape(p)
    ty = ty.reshape(p, 1)
    tx = tx.reshape(p, 1)

    def gat(idx):
        return jnp.take(flat, idx, axis=0).astype(jnp.float32)

    v00 = gat(idx00)
    v01 = gat(idx00 + 1)
    v10 = gat(idx00 + band_w)
    v11 = gat(idx00 + band_w + 1)

    w00 = (1 - ty) * (1 - tx)
    w01 = (1 - ty) * tx
    w10 = ty * (1 - tx)
    w11 = ty * tx

    # Recomputed forward patches (fp32), shaped for the MXU contraction.
    patches = w00 * v00 + w01 * v01 + w10 * v10 + w11 * v11   # (p, tc)
    lhs = patches.reshape(tile_h * tile_w, k2 * tile_c)

    g = g_ref[0].astype(jnp.float32).reshape(tile_h * tile_w, -1)
    wblk = w_ref[0].astype(jnp.float32)                # (k2*tc, M)

    # d_weights: patches^T @ g, accumulated fp32 across the whole grid.
    dw_acc[cc] += jnp.dot(lhs.T, g, preferred_element_type=jnp.float32)
    if dw_flush_every_step:
        # Interpret-mode cadence: the interpreter re-materializes the
        # output block buffer on every revisit, so the accumulator must
        # be mirrored into dw_ref each step to survive the copy-out.
        dw_ref[0, 0] = dw_acc[cc]
    else:
        # Compiled cadence (ROADMAP "d_weights flush"): mirror the
        # accumulator only on the LAST spatial grid step of THIS
        # CORE'S batch shard — the final revisit of each (core,
        # C-chunk) block is the only copy-out that has to carry the
        # complete partial sum, cutting the modeled dw write traffic
        # by n_per_core*h_tiles*w_tiles per core (see
        # ``tiling.dcl_backward_hbm_bytes``).  The batch/spatial grid
        # axes are sequential ("arbitrary") within a core, so the
        # core-local last step is well defined; the core axis itself
        # is parallel, which is exactly why the flush condition must
        # not reference it.
        last_spatial = ((b == pl.num_programs(1) - 1)
                        & (j == pl.num_programs(2) - 1)
                        & (ww == pl.num_programs(3) - 1))

        @pl.when(last_spatial)
        def _flush_dw():
            dw_ref[0, 0] = dw_acc[cc]

    # d_patches: g @ W^T  -> (p, tc).
    dp = jnp.dot(g, wblk.T, preferred_element_type=jnp.float32)
    dp = dp.reshape(tile_h * tile_w, k2, tile_c).reshape(p, tile_c)

    # d_offsets: contract d_patches against the corner-value derivatives.
    dval_dy = (1 - tx) * (v10 - v00) + tx * (v11 - v01)
    dval_dx = (1 - ty) * (v01 - v00) + ty * (v11 - v10)
    doff_y = jnp.sum(dp * dval_dy, axis=-1).reshape(tile_h, tile_w, k2)
    doff_x = jnp.sum(dp * dval_dx, axis=-1).reshape(tile_h, tile_w, k2)
    doff_acc[...] += jnp.stack([doff_y, doff_x], axis=-1)

    @pl.when(cc == c_steps - 1)
    def _flush_doff():
        # Eq. 5 clamp VJP: gradient flows only where the raw offset is
        # inside [-B, B] (ties are measure-zero; see module docstring).
        mask = ((off_raw >= -b_.offset_bound)
                & (off_raw <= b_.offset_bound)).astype(jnp.float32)
        doff_ref[0] = (doff_acc[...] * mask).reshape(
            tile_h, tile_w, 2 * k2).astype(doff_ref.dtype)

    # d_input: in-band scatter of the four bilinear corners, then an
    # async-copy read-modify-write flush of this tile's Eq. 6 band.
    dxb = jnp.zeros((band_h * band_w, tile_c), jnp.float32)
    dxb = dxb.at[idx00].add(w00 * dp)
    dxb = dxb.at[idx00 + 1].add(w01 * dp)
    dxb = dxb.at[idx00 + band_w].add(w10 * dp)
    dxb = dxb.at[idx00 + band_w + 1].add(w11 * dp)

    rmw_dma(write=False).wait()
    rmw_ref[...] = (rmw_ref[...].astype(jnp.float32)
                    + dxb.reshape(band_h, band_w, tile_c)
                    ).astype(rmw_ref.dtype)
    wr = rmw_dma(write=True)
    wr.start()
    wr.wait()


@functools.partial(
    jax.jit,
    static_argnames=("kernel_size", "stride", "dilation", "offset_bound",
                     "tile_h", "tile_w", "tile_c", "cores", "interpret",
                     "dw_flush_every_step"))
def deform_conv_bwd_zerocopy(x_pad: Array, offsets: Array, g: Array,
                             w_tiles: Array, *, kernel_size: int,
                             stride: int, dilation: int, offset_bound: float,
                             tile_h: int, tile_w: int,
                             tile_c: int | None = None,
                             cores: int = 1,
                             interpret: bool = True,
                             dw_flush_every_step: bool | None = None
                             ) -> tuple[Array, Array, Array]:
    """Fused backward over the whole padded input (zero-copy dataflow).

    x_pad:   (N, Hp, Wp, C) zero-padded input, left whole in ANY/HBM
    offsets: (N, Ho, Wo, 2*K*K) *raw* offsets, Ho/Wo multiples of tiles
    g:       (N, Ho, Wo, M) output cotangent
    w_tiles: (C//tile_c, K*K*tile_c, M) — ``plan.tile_weights`` layout
    returns: (dx_pad fp-matched to x_pad, d_offsets, dw_tiles fp32) —
             dx_pad includes the zero padding (caller un-pads), dw_tiles
             is in the same blocked layout as ``w_tiles``.

    ``cores`` splits the batch axis into per-core shards (Megacore
    ``parallel`` semantics on the leading grid axis; must divide N —
    ``ops.check_batch_split`` raises the friendly error).  Each core
    emits a partial ``d_weights`` block; the ``sum`` epilogue here
    reduces them (exact no-op at cores=1).

    ``dw_flush_every_step`` controls the d_weights accumulator->output
    mirror cadence: every grid step (required under the interpreter,
    which re-materializes output block buffers per revisit) or only on
    the core-local last spatial step (the compiled cadence,
    n_per_core*h_tiles*w_tiles fewer modeled dw writes per core).
    ``None`` follows ``interpret``.
    """
    n, hp, wp, c = x_pad.shape
    _, ho, wo, _ = offsets.shape
    assert ho % tile_h == 0 and wo % tile_w == 0, (ho, wo, tile_h, tile_w)
    assert g.shape[:3] == (n, ho, wo), (g.shape, offsets.shape)
    assert cores >= 1 and n % cores == 0, (n, cores)
    n_per_core = n // cores
    h_tiles, w_tiles_n = ho // tile_h, wo // tile_w
    k2 = kernel_size * kernel_size
    tc = tile_c or c
    assert c % tc == 0
    c_steps = c // tc
    assert w_tiles.shape[0] == c_steps and w_tiles.shape[1] == k2 * tc
    m = w_tiles.shape[2]
    plan = DCLPlan(
        band=BandSpec(kernel_size=kernel_size, stride=stride,
                      dilation=dilation, offset_bound=offset_bound,
                      tile_h=tile_h, tile_w=tile_w),
        tile_c=tc, tile_m=None, cores=cores)
    band_h, band_w = plan.band.band_h, plan.band.band_w
    plan.band.check_padded(hp, wp, h_tiles, w_tiles_n)
    if dw_flush_every_step is None:
        dw_flush_every_step = interpret

    dx0 = jnp.zeros_like(x_pad)
    out_shapes = (
        jax.ShapeDtypeStruct((n, hp, wp, c), x_pad.dtype),        # dx_pad
        jax.ShapeDtypeStruct((n, ho, wo, 2 * k2), offsets.dtype),  # d_off
        # Per-core d_weights partials, reduced by the epilogue below.
        jax.ShapeDtypeStruct((cores, c_steps, k2 * tc, m), jnp.float32),
    )
    npc = n_per_core
    dxp, doff, dw_partials = pl.pallas_call(
        functools.partial(
            _bwd_zerocopy_kernel, plan, n_per_core=npc,
            dw_flush_every_step=dw_flush_every_step),
        grid=(cores, n_per_core, h_tiles, w_tiles_n, c_steps),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),      # dx seed (aliased)
            pl.BlockSpec(memory_space=pl.ANY),      # whole padded input
            pl.BlockSpec((1, tile_h, tile_w, 2 * k2),
                         lambda co, b, j, ww, cc: (co * npc + b, j, ww, 0)),
            pl.BlockSpec((1, tile_h, tile_w, m),
                         lambda co, b, j, ww, cc: (co * npc + b, j, ww, 0)),
            pl.BlockSpec((1, k2 * tc, m),
                         lambda co, b, j, ww, cc: (cc, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),      # dx_pad (aliased)
            pl.BlockSpec((1, tile_h, tile_w, 2 * k2),
                         lambda co, b, j, ww, cc: (co * npc + b, j, ww, 0)),
            pl.BlockSpec((1, 1, k2 * tc, m),
                         lambda co, b, j, ww, cc: (co, cc, 0, 0)),
        ),
        out_shape=out_shapes,
        scratch_shapes=[
            # Band scratch follows x's dtype (the fp32 training path;
            # plan.band_scratch() would pin float32 — keep it general).
            pltpu.VMEM((N_BUFFERS, band_h, band_w, tc), x_pad.dtype),
            pltpu.VMEM((band_h, band_w, tc), x_pad.dtype),
            # Private per core: hardware gives each core its own scratch
            # instance; interpret mode runs core subgrids sequentially
            # and the per-core init zeroes it between shards.
            pltpu.VMEM((c_steps, k2 * tc, m), jnp.float32),
            pltpu.VMEM((tile_h, tile_w, k2, 2), jnp.float32),
            plan.dma_sem(),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        input_output_aliases={0: 0},
        compiler_params=compiler_params(("parallel", "arbitrary",
                                         "arbitrary", "arbitrary",
                                         "arbitrary")),
        interpret=interpret,
    )(dx0, x_pad, offsets, g, w_tiles)
    # Cheap epilogue: reduce the per-core d_weights partials.  Exact at
    # cores=1 (singleton-axis sum); at cores>1 this is the only place
    # the fp32 summation order differs from the sequential kernel.
    return dxp, doff, jnp.sum(dw_partials, axis=0)
