"""Unified band-pipeline kernel emitter (the Eq. 6 dataflow, once).

Every bounded DCL kernel in this package runs the same dataflow: the
padded input stays whole in ``ANY``/HBM, and per (batch, row-tile,
width-tile[, M-tile], C-chunk) grid step one Eq. 6 ``(band_h, band_w)``
band chunk streams into double-buffered VMEM scratch via
``pltpu.make_async_copy`` while the previous chunk's sampling + MXU work
rides on top.  Before this module, four kernels (``deform_sample``,
``deform_conv_fused``, ``deform_conv_q``, ``deform_conv_bwd``) each
re-implemented that staging, the grid construction, and the accumulator
flush cadence by hand.  Now there is exactly one emitter:

* ``BandSpec`` — the Eq. 6 geometry of one call (kernel size, stride,
  dilation, trained offset bound, spatial tiles) with the derived band
  extents and halo;
* ``DCLPlan`` — one kernel instantiation: the ``BandSpec`` plus channel
  tiles, the staged-band dtype (fp32 or int8 — the band DMA geometry is
  dtype-independent, only the element width changes), the MXU
  accumulator dtype (fp32 or exact int32), the epilogue
  (``cast`` / ``dequant`` / ``requant`` — fp32 emission, fused
  per-channel dequant, or int8 re-emission for layer chaining), the
  optional fused offset-conv stage, and the Megacore ``cores`` axis of
  the backward grid;
* ``BandStager`` — the double-buffered ``make_async_copy`` pipeline
  (warmup / prefetch / wait), used by every kernel body;
* ``forward_call`` — emits the whole family of forward kernels
  (sample-only, fused fp32, fused int8, int8 chain) from a ``DCLPlan``;
  the backward kernel (``deform_conv_bwd``) builds its grid, scratch
  and staging from the same plan.

New capabilities the emitter unlocks (ROADMAP int8 follow-ups):

* **fused int8 offset-conv stage** (``DCLPlan.fuse_offsets``): the
  offset-generating 3x3 conv has a *regular* receptive field, which is
  a strict subset of the Eq. 6 band (the band covers the deformed taps,
  the offset conv needs only the undeformed ones).  With the whole
  channel extent staged (``c_steps == 1`` — enforced) the kernel
  computes the offsets from the already-staged int8 band with nine
  static window loads per row + an int8 MXU contraction and a fp32
  dequant: no separate fp32 offset pass, and the offsets never exist
  in HBM.
* **int8 output emission with per-channel requant**
  (``epilogue="requant"``): the int32 accumulator is rescaled by
  ``s_x * s_w[m] / s_y`` (bias folded as ``b[m] / s_y``), rounded and
  clipped onto the next layer's activation grid, and emitted int8 —
  back-to-back DCLs chain int8 -> int8 with no fp32 HBM round-trip
  between layers (``ops.deform_conv_chain``).

Geometry helpers (``band_geometry``, ``corner_geometry``) and the
shifted-window sampler (``sample_row_taps``) live here too, so the
emitter is self-contained.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

N_BUFFERS = 2     # double buffering: fetch band i+1 while computing band i
LANES = 128       # vreg lane width: the sampler's channel chunk


# ---------------------------------------------------------------------------
# Eq. 6 geometry
# ---------------------------------------------------------------------------

def band_geometry(*, kernel_size: int, stride: int, dilation: int,
                  offset_bound: float, tile_h: int) -> tuple[int, int]:
    """(halo, band_h): halo = ceil(B)+1 rows each side (bilinear +1);
    band_h per Eq. 6 with the bilinear corner accounted.  The same
    algebra applies along width with ``tile_h`` replaced by ``tile_w``.
    Delegates to ``core.tiling.band_extent`` so the kernels and the
    traffic/VMEM models can never disagree on the geometry.
    """
    from repro.core.tiling import band_extent
    hb = int(math.ceil(offset_bound))
    band_h = band_extent(tile_h, kernel_size=kernel_size, stride=stride,
                         dilation=dilation, offset_bound=offset_bound)
    return hb, band_h


def _tap_grid(*, kernel_size: int, stride: int, dilation: int, halo: int,
              tile_h: int, tile_w: int):
    """Band-local *undeformed* tap positions of one output tile (int32):
    ``rows`` (tile_h, 1, K*K) and ``cols`` (1, tile_w, K*K), with the
    band starting ``halo`` rows/cols before the first tap.  The single
    source of the Eq. 6 base algebra — shared by the bilinear corner
    geometry (which adds the offsets on top) and the fused offset-conv
    stage (which reads exactly these positions)."""
    k, s, d = kernel_size, stride, dilation
    k2 = k * k
    ky = jax.lax.broadcasted_iota(jnp.int32, (k, k), 0).reshape(k2) * d
    kx = jax.lax.broadcasted_iota(jnp.int32, (k, k), 1).reshape(k2) * d
    oy = jax.lax.iota(jnp.int32, tile_h) * s + halo
    ox = jax.lax.iota(jnp.int32, tile_w) * s + halo
    rows = oy[:, None, None] + ky[None, None, :]
    cols = ox[None, :, None] + kx[None, None, :]
    return rows, cols


def corner_geometry(off, *, kernel_size: int, stride: int, dilation: int,
                    offset_bound: float, tile_h: int, wo: int):
    """Bilinear corner geometry for one output tile, in band-local coords.

    off: (tile_h, wo, K*K, 2) raw offsets (clamped here to the Eq. 5 bound).
    Returns (y0, x0, ty, tx): int32 top-left corner indices and fp32
    fractional coefficients, each (tile_h, wo, K*K).  Shared between the
    backward kernel of ``deform_conv_bwd.py`` and, row by row through
    the same ``floor``/fraction arithmetic (``_floor_frac``), by the
    forward sampler — the same bound ``B`` that keeps forward samples
    in-band keeps backward scatters in-band.
    """
    k, s, d = kernel_size, stride, dilation
    hb = int(math.ceil(offset_bound))       # static: offset_bound is Python

    # Positions/coefficients in fp32 (address generation is full precision
    # even on a bf16 datapath).
    off = jnp.clip(off.astype(jnp.float32), -offset_bound, offset_bound)

    # Base tap positions in band-local (pre-padded) coordinates: the band
    # starts ``hb`` rows above the first tap row, and the width axis is
    # pre-padded by (pad + hb) so the same formula applies.
    rows, cols = _tap_grid(kernel_size=k, stride=s, dilation=d, halo=hb,
                           tile_h=tile_h, tile_w=wo)
    pos_y = rows.astype(jnp.float32) + off[..., 0]    # (tile_h, wo, k2)
    pos_x = cols.astype(jnp.float32) + off[..., 1]

    y0f = jnp.floor(pos_y)
    x0f = jnp.floor(pos_x)
    ty = pos_y - y0f
    tx = pos_x - x0f
    return y0f.astype(jnp.int32), x0f.astype(jnp.int32), ty, tx


def _floor_frac(base, off):
    """Bilinear corner of ``base + off``: the relative floor
    ``floor(base + off) - base`` (an exact small integer, in fp32) and
    the fractional coefficient.  ``base`` is an integer band-local tap
    position; the sum is formed in fp32 exactly as ``corner_geometry``
    forms it, so both sides see the same coefficients."""
    base_f = jnp.asarray(base).astype(jnp.float32)
    pos = base_f + off
    p0 = jnp.floor(pos)
    return p0 - base_f, pos - p0


def _tent(rel, frac, shift):
    """Bilinear weight of one shift of the bounded window along one
    axis: ``1 - frac`` at the floor corner, ``frac`` at the next one, 0
    at every other shift."""
    return jnp.where(rel == shift, 1 - frac,
                     jnp.where(rel == shift - 1, frac, jnp.zeros_like(frac)))


def sample_row_taps(chunks, off, t, spec: "BandSpec"):
    """Bilinearly sample output row ``t`` of one tile from a staged band.

    chunks: refs of shape (band_h, band_w, lanes) — the staged band split
            into lane chunks (their concatenation along lanes is the C
            chunk); every load is a static shifted (and, for stride 2,
            strided) window, which Mosaic lowers without a gather
    off:    ref (tile_h, tile_w, 2*K*K) of raw offsets (clamped here to
            the Eq. 5 bound)
    t:      output row within the tile (traced scalar)
    returns K*K fp32 arrays of shape (tile_w, C-chunk), tap-major.

    Because the trained bound B is static, every sample of tap
    (ky, kx) lies in the (2*ceil(B)+2)^2 window of shifts around its
    undeformed position, so the sample is the tent-weighted sum of those
    shifted windows.  Only the bilinear corners carry non-zero weight,
    and they are met in the order (y0, x0), (y0, x0+1), (y0+1, x0),
    (y0+1, x0+1): values accumulate in fp32 with fp32 coefficients,
    corner by corner, as a gather of the four corners would.
    """
    k, s, d, hb = spec.kernel_size, spec.stride, spec.dilation, spec.halo
    bound = spec.offset_bound
    tw = spec.tile_w
    col_shifts = range(-hb, hb + 2)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tw, 1), 0) * s
    taps = []
    for ky in range(k):
        row0 = t * s + hb + ky * d
        for kx in range(k):
            tap = ky * k + kx
            oy = jnp.clip(off[t, :, pl.ds(2 * tap, 1)].astype(jnp.float32),
                          -bound, bound)
            ox = jnp.clip(off[t, :, pl.ds(2 * tap + 1, 1)].astype(
                jnp.float32), -bound, bound)
            rel_y, frac_y = _floor_frac(row0, oy)
            wx = [_tent(*_floor_frac(cols + (hb + kx * d), ox), dx)
                  for dx in col_shifts]

            # Row shifts loop (rows are a dynamic leading index); column
            # shifts stay static (sublane offsets must be).  Shifts are
            # visited row-major, so the corners accumulate in gather
            # order and the zero-weight shifts add exact zeros.
            def _row_shift(i, accs, row0=row0, kx=kx, rel_y=rel_y,
                           frac_y=frac_y, wx=wx):
                dy = i - hb
                wy = _tent(rel_y, frac_y, dy.astype(jnp.float32))
                accs = list(accs)
                for ix, dx in enumerate(col_shifts):
                    wgt = wy * wx[ix]
                    for c, chunk in enumerate(chunks):
                        v = chunk[row0 + dy,
                                  pl.ds(hb + kx * d + dx, tw, stride=s), :]
                        accs[c] = accs[c] + v.astype(jnp.float32) * wgt
                return tuple(accs)
            zeros = tuple(jnp.zeros((tw, ch.shape[-1]), jnp.float32)
                          for ch in chunks)
            parts = jax.lax.fori_loop(0, len(col_shifts), _row_shift, zeros)
            taps.append(parts[0] if len(parts) == 1
                        else jnp.concatenate(parts, axis=-1))
    return taps


def for_each_row(spec: "BandSpec", body) -> None:
    """Run ``body(t)`` for every output row of the tile — a loop, not an
    unrolled tile, so the emitted kernel's size does not grow with
    ``tile_h``."""
    def _row(t, carry):
        body(t)
        return carry
    jax.lax.fori_loop(0, spec.tile_h, _row, 0)


def unpack_band(band, samp_ref) -> None:
    """Copy a staged band (band_h, band_w, C-chunk) of any dtype into
    the fp32 sampling scratch (n_chunks, band_h, band_w, lanes).

    Mosaic's shifted and strided window loads need 32-bit elements and,
    when strided, a 128-lane minor dimension, so the sampler reads this
    lane-chunked fp32 copy; the DMA itself stays at the band dtype (int8
    bands keep their 4x HBM saving).  int8 values are exact in fp32."""
    n_chunks, band_h, _, lanes = samp_ref.shape

    def _row(r, carry):
        for c in range(n_chunks):
            samp_ref[c, r] = band[r, :, pl.ds(c * lanes, lanes)].astype(
                jnp.float32)
        return carry
    jax.lax.fori_loop(0, band_h, _row, 0)


# ---------------------------------------------------------------------------
# Band staging: the double-buffered make_async_copy pipeline
# ---------------------------------------------------------------------------

def make_band_dma(x_hbm, band_ref, sem_ref, *, batch, row0, col0, c0,
                  band_h: int, band_w: int, tile_c: int, slot):
    """DMA descriptor for one (row-tile, width-tile, C-chunk) band:
    HBM -> VMEM scratch slot.  Reconstructed identically to start and to
    wait (the standard Pallas async-copy pattern)."""
    return pltpu.make_async_copy(
        x_hbm.at[batch,
                 pl.ds(row0, band_h),
                 pl.ds(col0, band_w),
                 pl.ds(c0, tile_c)],
        band_ref.at[slot],
        sem_ref.at[slot])


class BandStager:
    """Double-buffered Eq. 6 band staging for one (batch, row-tile,
    width-tile) grid position: chunk ``cc+1``'s HBM -> VMEM copy rides
    under chunk ``cc``'s sampling + MXU work.

    ``stage(cc, c_steps)`` is the whole pipeline (warmup at the first
    chunk, prefetch of the next, wait on the current) and returns a ref
    view of the staged band; kernels that need to interleave other DMAs
    (the backward's d_input read-modify-write) call ``warmup`` /
    ``prefetch`` / ``wait`` individually to keep their overlap structure
    explicit.
    """

    def __init__(self, x_hbm, band_ref, sem_ref, *, batch, row0, col0,
                 band_h: int, band_w: int, tile_c: int):
        self.x_hbm = x_hbm
        self.band_ref = band_ref
        self.sem_ref = sem_ref
        self.batch = batch
        self.row0 = row0
        self.col0 = col0
        self.band_h = band_h
        self.band_w = band_w
        self.tile_c = tile_c

    def dma(self, step, slot):
        return make_band_dma(
            self.x_hbm, self.band_ref, self.sem_ref, batch=self.batch,
            row0=self.row0, col0=self.col0, c0=step * self.tile_c,
            band_h=self.band_h, band_w=self.band_w, tile_c=self.tile_c,
            slot=slot)

    def warmup(self):
        """Start the first chunk's fetch (call under ``cc == 0``)."""
        self.dma(0, 0).start()

    def prefetch(self, cc, c_steps):
        """Start chunk ``cc+1``'s fetch into the other buffer slot."""
        @pl.when(cc + 1 < c_steps)
        def _prefetch():
            self.dma(cc + 1, (cc + 1) % N_BUFFERS).start()

    def wait(self, cc):
        """Block on chunk ``cc`` and return its staged band view."""
        self.dma(cc, cc % N_BUFFERS).wait()
        return self.band_ref[cc % N_BUFFERS]

    def stage(self, cc, c_steps):
        @pl.when(cc == 0)
        def _warmup():
            self.warmup()
        self.prefetch(cc, c_steps)
        self.dma(cc, cc % N_BUFFERS).wait()
        return self.band_ref.at[cc % N_BUFFERS]




# ---------------------------------------------------------------------------
# BandSpec / DCLPlan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BandSpec:
    """Eq. 6 band geometry of one bounded DCL call (hashable/static)."""
    kernel_size: int
    stride: int
    dilation: int
    offset_bound: float
    tile_h: int
    tile_w: int

    @property
    def k2(self) -> int:
        return self.kernel_size * self.kernel_size

    @property
    def halo(self) -> int:
        return int(math.ceil(self.offset_bound))

    @property
    def band_h(self) -> int:
        return band_geometry(kernel_size=self.kernel_size,
                             stride=self.stride, dilation=self.dilation,
                             offset_bound=self.offset_bound,
                             tile_h=self.tile_h)[1]

    @property
    def band_w(self) -> int:
        return band_geometry(kernel_size=self.kernel_size,
                             stride=self.stride, dilation=self.dilation,
                             offset_bound=self.offset_bound,
                             tile_h=self.tile_w)[1]

    @property
    def stage_w(self) -> int:
        """Columns the forward kernels DMA per band: ``band_w`` rounded
        up to whole sublane tiles (Mosaic refuses int8 band copies of a
        ragged width)."""
        from repro.core.tiling import staged_width
        return staged_width(self.band_w)

    def check_padded(self, hp: int, wp: int, h_tiles: int,
                     w_tiles: int) -> None:
        s = self.stride
        assert (h_tiles - 1) * self.tile_h * s + self.band_h <= hp, \
            "underpadded H"
        assert (w_tiles - 1) * self.tile_w * s + self.stage_w <= wp, \
            "underpadded W"


@dataclasses.dataclass(frozen=True)
class DCLPlan:
    """One kernel instantiation of the band pipeline (hashable/static).

    ``tile_m=None`` selects the sample-only kernel (stage 1: no MXU
    contraction, patches are the output).  ``band_dtype`` is the staged
    band's element type (``"float32"`` or ``"int8"`` — one geometry, two
    densities); ``acc_dtype`` the MXU accumulator (``"float32"`` or the
    exact ``"int32"`` of the s8 x s8 datapath).  ``epilogue`` selects the
    flush: ``"cast"`` (fp32 accumulator -> output dtype), ``"dequant"``
    (int32 -> fp32 via the per-channel combined scale), ``"requant"``
    (int32 -> int8 on the next layer's grid — layer chaining).
    ``fuse_offsets`` computes the offsets in-kernel from the staged band
    (requires ``c_steps == 1``); ``cores`` is the Megacore core axis of
    the backward grid (forward kernels keep it at 1).
    """
    band: BandSpec
    tile_c: int
    tile_m: int | None = None
    band_dtype: str = "float32"
    acc_dtype: str = "float32"
    epilogue: str = "cast"
    fuse_offsets: bool = False
    cores: int = 1

    def __post_init__(self):
        assert self.epilogue in ("cast", "dequant", "requant"), self.epilogue
        if self.band_dtype not in ("float32", "bfloat16", "float16",
                                   "int8"):
            raise ValueError(
                f"unsupported band dtype {self.band_dtype!r}; the band "
                f"pipeline stages float32, bfloat16, float16 or int8 "
                f"bands — cast the input first")
        assert self.acc_dtype in ("float32", "int32"), self.acc_dtype

    @property
    def contract(self) -> bool:
        return self.tile_m is not None

    @property
    def lanes(self) -> int:
        """Lane width of one sampling chunk: 128 where the channel tile
        splits into whole vreg lanes, else the whole (small) tile."""
        return LANES if self.tile_c % LANES == 0 else self.tile_c

    def jnp_band_dtype(self):
        return jnp.dtype(self.band_dtype)

    def jnp_acc_dtype(self):
        return jnp.int32 if self.acc_dtype == "int32" else jnp.float32

    def jnp_patch_dtype(self):
        """The MXU operand type of the sampled patches: int8 values
        re-rounded onto the activation grid, or fp32."""
        return jnp.int8 if self.band_dtype == "int8" else jnp.float32

    # -- shared scratch/grid builders ---------------------------------
    def band_scratch(self):
        # Fused-offset plans stage the whole C extent once per spatial
        # tile (c_steps == 1, fetched at mm == 0 only) — there is
        # nothing to overlap, so a single slot halves the kernel's
        # largest VMEM buffer.
        n_buf = 1 if self.fuse_offsets else N_BUFFERS
        return pltpu.VMEM((n_buf, self.band.band_h, self.band.stage_w,
                           self.tile_c), self.jnp_band_dtype())

    def sample_scratch(self):
        """The lane-chunked fp32 copy of the staged band that the
        sampler reads (``unpack_band``)."""
        return pltpu.VMEM((self.tile_c // self.lanes, self.band.band_h,
                           self.band.stage_w, self.lanes), jnp.float32)

    def dma_sem(self):
        return pltpu.SemaphoreType.DMA((N_BUFFERS,))

    def stager(self, x_hbm, band_ref, sem_ref, *, batch, row0, col0):
        return BandStager(x_hbm, band_ref, sem_ref, batch=batch, row0=row0,
                          col0=col0, band_h=self.band.band_h,
                          band_w=band_ref.shape[-2], tile_c=self.tile_c)


def offset_conv_row(plan: DCLPlan, chunks, t, woff_ref, off_scale_ref,
                    off_bias_ref):
    """Fused offset-conv stage for output row ``t``: offsets from the
    already-staged band.

    The offset conv's taps are the *undeformed* grid positions — static
    windows of the Eq. 6 band (band-local row ``t*s + hb + ky*d``,
    columns ``hb + kx*d + u*s``) — so one int8 MXU contraction per row
    produces the raw offsets without any extra HBM traffic:

        off[t, u, :] = (sum_{ky,kx,c} q_x[tap] * q_woff) * s_x*s_woff + b

    (exact int32 accumulation, fp32 dequant).  Requires the whole
    channel extent staged (``c_steps == 1`` — the offsets must be
    complete before the first bilinear sample consumes them).
    Returns raw fp32 offsets (tile_w, 2*K*K); the Eq. 5 clamp happens in
    the sampler exactly as for streamed offsets.
    """
    b = plan.band
    k, s, d, hb = b.kernel_size, b.stride, b.dilation, b.halo
    taps = []
    for ky in range(k):
        for kx in range(k):
            parts = [ch[t * s + hb + ky * d,
                        pl.ds(hb + kx * d, b.tile_w, stride=s), :]
                     for ch in chunks]
            taps.extend(parts)
    lhs = jnp.concatenate(taps, axis=-1).astype(jnp.int8)
    acc = jnp.dot(lhs, woff_ref[0], precision=jax.lax.Precision.DEFAULT,
                  preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * off_scale_ref[...] + off_bias_ref[...]


# ---------------------------------------------------------------------------
# Unified forward kernel (sample-only / fused fp32 / fused int8 / chain)
# ---------------------------------------------------------------------------

def _forward_kernel(plan: DCLPlan, has_scale: bool, has_bias: bool, *refs):
    b = plan.band
    k2 = b.k2
    it = iter(refs)
    x_hbm = next(it)
    off_ref = None if plan.fuse_offsets else next(it)
    woff_ref = next(it) if plan.fuse_offsets else None
    off_scale_ref = next(it) if plan.fuse_offsets else None
    off_bias_ref = next(it) if plan.fuse_offsets else None
    w_ref = next(it) if plan.contract else None
    scale_ref = next(it) if has_scale else None
    bias_ref = next(it) if has_bias else None
    out_ref = next(it)
    band_ref = next(it)
    samp_ref = next(it)
    patch_ref = next(it) if plan.contract else None
    acc_ref = next(it) if plan.contract else None
    off_scratch = next(it) if plan.fuse_offsets else None
    sem_ref = next(it)

    i = pl.program_id(0)
    j = pl.program_id(1)
    ww = pl.program_id(2)
    mm = pl.program_id(3) if plan.contract else None
    c_axis = 4 if plan.contract else 3
    cc = pl.program_id(c_axis)
    c_steps = pl.num_programs(c_axis)

    stager = plan.stager(x_hbm, band_ref, sem_ref, batch=i,
                         row0=j * (b.tile_h * b.stride),
                         col0=ww * (b.tile_w * b.stride))
    chunks = [samp_ref.at[c] for c in range(samp_ref.shape[0])]

    if plan.contract:
        @pl.when(cc == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

    if plan.fuse_offsets:
        # Fused-offset plans stage the whole C extent (c_steps == 1), so
        # the band — and the offsets computed from it — are identical
        # across the sequential M-tile axis: fetch, unpack and run the
        # offset stage once per spatial tile (mm == 0; the scratch
        # persists), matching dcl_chain_hbm_bytes, which charges the
        # band once per spatial tile.
        @pl.when(mm == 0)
        def _fetch_band():
            stager.warmup()
            stager.dma(0, 0).wait()
            unpack_band(band_ref.at[0], samp_ref)

            def _offsets(t):
                off_scratch[t] = offset_conv_row(
                    plan, chunks, t, woff_ref, off_scale_ref, off_bias_ref)
            for_each_row(b, _offsets)
        off = off_scratch
    else:
        # Double buffering: the next C-chunk's band streams in underneath
        # this chunk's sampling + MXU work.
        unpack_band(stager.stage(cc, c_steps), samp_ref)
        off = off_ref

    requant = plan.band_dtype == "int8"

    def _row(t):
        taps = sample_row_taps(chunks, off, t, b)
        if requant:
            # The quantized datapath's patch requantization: the convex
            # bilinear mix of int8 values stays in [-127, 127], so this
            # is a pure round onto the activation grid.
            taps = [jnp.round(v) for v in taps]
        if plan.contract:
            patch_ref[t] = jnp.concatenate(taps, axis=-1).astype(
                patch_ref.dtype)
        else:
            out_ref[0, t] = jnp.stack(taps, axis=1).astype(out_ref.dtype)
    for_each_row(b, _row)

    if not plan.contract:
        return

    # (th*tw, k2*tc) @ (k2*tc, tm) on the MXU — fp32 accumulation of fp32
    # operands on the fp32 datapath, exact int32 on the s8 x s8 datapath.
    # The precision is explicit so a caller's default_matmul_precision
    # never reaches the kernel (Mosaic refuses fp32 contraction of int8).
    lhs = patch_ref[...].reshape(b.tile_h * b.tile_w, k2 * plan.tile_c)
    precision = (jax.lax.Precision.HIGHEST if plan.acc_dtype == "float32"
                 else jax.lax.Precision.DEFAULT)
    acc_ref[...] += jnp.dot(lhs, w_ref[0], precision=precision,
                            preferred_element_type=plan.jnp_acc_dtype())

    @pl.when(cc == c_steps - 1)
    def _flush():
        tm = out_ref.shape[-1]
        acc = acc_ref[...]
        if plan.epilogue == "cast":
            y = acc
        else:
            y = acc.astype(jnp.float32) * scale_ref[...]
            if has_bias:
                y = y + bias_ref[...]
            if plan.epilogue == "requant":
                y = jnp.clip(jnp.round(y), -127, 127)
        out_ref[0] = y.reshape(b.tile_h, b.tile_w, tm).astype(out_ref.dtype)


def compiler_params(semantics: tuple[str, ...]):
    """Mosaic parameters of every bounded kernel: the grid semantics and
    the scoped VMEM limit the Sec. 3.2 chooser budgets tiles against
    (``core.tiling.VMEM_LIMIT_BYTES``), so a tile the chooser accepts is
    a tile Mosaic accepts."""
    from repro.core.tiling import VMEM_LIMIT_BYTES
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def forward_call(plan: DCLPlan, x_pad: Array, offsets: Array | None,
                 w_tiles: Array | None = None, *,
                 scale: Array | None = None, bias: Array | None = None,
                 woff_tiles: Array | None = None,
                 off_scale: Array | None = None,
                 off_bias: Array | None = None,
                 ho: int | None = None, wo: int | None = None,
                 out_dtype=None, interpret: bool = True) -> Array:
    """Emit + run one forward band-pipeline kernel from a ``DCLPlan``.

    x_pad:   (N, Hp, Wp, C) zero-padded input, left whole in ANY/HBM
             (fp32 or int8 per ``plan.band_dtype``)
    offsets: (N, Ho, Wo, 2*K*K) raw offsets — ``None`` iff the plan
             fuses the offset-conv stage (then ``ho``/``wo`` name the
             padded output extent and ``woff_tiles``/``off_scale``/
             ``off_bias`` carry the quantized offset conv)
    w_tiles: (C//tile_c, K*K*tile_c, M) ``plan``-blocked deform weights
             (``None`` for the sample-only kernel)
    scale/bias: (1, M) fp32 epilogue operands (dequant/requant plans)
    returns: (N, Ho, Wo, M) — or (N, Ho, Wo, K*K, C) patches when the
             plan has no contraction stage.
    """
    b = plan.band
    n, hp, wp, c = x_pad.shape
    if offsets is not None:
        _, ho, wo, _ = offsets.shape
    assert ho is not None and wo is not None
    assert ho % b.tile_h == 0 and wo % b.tile_w == 0, \
        (ho, wo, b.tile_h, b.tile_w)
    h_tiles, w_tiles_n = ho // b.tile_h, wo // b.tile_w
    k2 = b.k2
    tc = plan.tile_c
    assert c % tc == 0, (c, tc)
    c_steps = c // tc
    assert x_pad.dtype == plan.jnp_band_dtype(), \
        (x_pad.dtype, plan.band_dtype)
    if plan.fuse_offsets:
        assert c_steps == 1, (
            "fused offset-conv stage needs the whole channel extent "
            "staged (c_steps == 1)")
        assert woff_tiles is not None and off_scale is not None \
            and off_bias is not None
    b.check_padded(hp, wp, h_tiles, w_tiles_n)

    # Offsets stream as (N*Ho, Wo, 2*K*K) row blocks: Mosaic cannot
    # window a 4-D block whose minor dim (2*K*K) is not lane-aligned,
    # but handles the same bytes as a 3-D block.
    if offsets is not None:
        offsets = offsets.reshape(n * ho, wo, 2 * k2)
    grid: tuple[int, ...]
    in_ops: list[Array] = [x_pad]
    in_specs: list = [pl.BlockSpec(memory_space=pl.ANY)]
    scratch = [plan.band_scratch(), plan.sample_scratch()]

    if plan.contract:
        assert w_tiles is not None
        assert w_tiles.shape[0] == c_steps and w_tiles.shape[1] == k2 * tc
        m = w_tiles.shape[2]
        tm = plan.tile_m or m
        assert m % tm == 0
        grid = (n, h_tiles, w_tiles_n, m // tm, c_steps)
        if not plan.fuse_offsets:
            in_ops.append(offsets)
            in_specs.append(pl.BlockSpec(
                (b.tile_h, b.tile_w, 2 * k2),
                lambda i, j, ww, mm, cc: (i * h_tiles + j, ww, 0)))
        else:
            in_ops += [woff_tiles, off_scale, off_bias]
            in_specs += [
                pl.BlockSpec((1, k2 * tc, 2 * k2),
                             lambda i, j, ww, mm, cc: (0, 0, 0)),
                pl.BlockSpec((1, 2 * k2),
                             lambda i, j, ww, mm, cc: (0, 0)),
                pl.BlockSpec((1, 2 * k2),
                             lambda i, j, ww, mm, cc: (0, 0)),
            ]
        in_ops.append(w_tiles)
        in_specs.append(pl.BlockSpec((1, k2 * tc, tm),
                                     lambda i, j, ww, mm, cc: (cc, 0, mm)))
        has_scale = scale is not None
        has_bias = bias is not None
        if has_scale:
            assert scale.shape == (1, m), scale.shape
            in_ops.append(scale)
            in_specs.append(pl.BlockSpec((1, tm),
                                         lambda i, j, ww, mm, cc: (0, mm)))
        if has_bias:
            assert bias.shape == (1, m), bias.shape
            in_ops.append(bias)
            in_specs.append(pl.BlockSpec((1, tm),
                                         lambda i, j, ww, mm, cc: (0, mm)))
        if plan.epilogue != "cast":
            assert has_scale, "dequant/requant epilogues need a scale"
        if out_dtype is None:
            out_dtype = jnp.int8 if plan.epilogue == "requant" \
                else jnp.float32
        out_specs = pl.BlockSpec((1, b.tile_h, b.tile_w, tm),
                                 lambda i, j, ww, mm, cc: (i, j, ww, mm))
        out_shape = jax.ShapeDtypeStruct((n, ho, wo, m), out_dtype)
        scratch.append(pltpu.VMEM((b.tile_h, b.tile_w, k2 * tc),
                                  plan.jnp_patch_dtype()))
        scratch.append(pltpu.VMEM((b.tile_h * b.tile_w, tm),
                                  plan.jnp_acc_dtype()))
        if plan.fuse_offsets:
            scratch.append(pltpu.VMEM((b.tile_h, b.tile_w, 2 * k2),
                                      jnp.float32))
        semantics = ("parallel", "parallel", "parallel", "arbitrary",
                     "arbitrary")
    else:
        assert not plan.fuse_offsets, "sample-only plans stream offsets"
        has_scale = has_bias = False
        grid = (n, h_tiles, w_tiles_n, c_steps)
        in_ops.append(offsets)
        in_specs.append(pl.BlockSpec((b.tile_h, b.tile_w, 2 * k2),
                                     lambda i, j, ww, cc: (i * h_tiles + j,
                                                           ww, 0)))
        out_specs = pl.BlockSpec((1, b.tile_h, b.tile_w, k2, tc),
                                 lambda i, j, ww, cc: (i, j, ww, 0, cc))
        out_shape = jax.ShapeDtypeStruct((n, ho, wo, k2, c),
                                         out_dtype or x_pad.dtype)
        semantics = ("parallel", "parallel", "parallel", "arbitrary")

    scratch.append(plan.dma_sem())
    return pl.pallas_call(
        functools.partial(_forward_kernel, plan, has_scale, has_bias),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=compiler_params(semantics),
        interpret=interpret,
    )(*in_ops)
