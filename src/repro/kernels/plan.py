"""Plan builder + dispatch bodies for the bounded DCL kernel family.

``ops.py`` is the public API surface (argument checking, mesh
resolution, the ``jax.custom_vjp`` wiring); this module is everything
between that surface and the ``band_pipeline`` emitter:

* ``DCSpec`` — the hashable static configuration of one bounded call
  (the custom-VJP ``nondiff`` argument, shared by the single-device and
  the shard_map VJPs);
* tile resolution (``resolve_tiles`` — the memoized Sec. 3.2 chooser
  bridge) and the weight blocking (``tile_weights``/``untile_weights``);
* input preparation (``pad_zerocopy`` / ``zerocopy_inputs`` — one code
  path for forward and backward so the backward's un-pad slice can
  never disagree with the forward's padded geometry; ``pad_and_band``
  for the legacy banded dataflow);
* the runners: ``bounded_forward`` / ``bounded_backward`` (fp32,
  both dataflows), ``int8_forward`` (the quantized inference datapath),
  and ``chain_forward`` (the int8 -> int8 layer-chaining datapath:
  fused offset-conv stage + per-channel requant emission).

Everything here is dataflow plumbing — the kernels themselves are
emitted by ``band_pipeline`` from ``DCLPlan``s.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from repro.core.tiling import (LayerShape, choose_kernel_tiles, out_hw,
                               staged_width)
from .band_pipeline import band_geometry
from .deform_conv_bwd import deform_conv_bwd_zerocopy
from .deform_conv_fused import (deform_conv_fused_banded,
                                deform_conv_fused_zerocopy)
from .deform_conv_q import (deform_conv_fused_zerocopy_chain,
                            deform_conv_fused_zerocopy_q)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class DCSpec:
    """Hashable static configuration of one bounded deform_conv call."""
    kernel_size: int
    stride: int
    dilation: int
    offset_bound: float
    tile_h: int | None
    tile_w: int | None
    tile_c: int | None
    tile_m: int | None
    dataflow: str
    interpret: bool
    cores: int = 1          # Megacore batch split of the backward grid
    # d_weights flush cadence of the backward kernel: None defers to
    # the kernel default (every-step under interpret, last-spatial-step
    # compiled) or, when a tuned cache is installed, to the measured
    # winner of the autotuner (ISSUE 9).
    dw_flush_every_step: bool | None = None


# ---------------------------------------------------------------------------
# Weight blocking
# ---------------------------------------------------------------------------

def tile_weights(w: Array, tile_c: int) -> Array:
    """(K*K, C, M) deform weights -> (C//tile_c, K*K*tile_c, M) blocks
    so the fused kernel's C-step reads one contiguous VMEM block."""
    k2, c, m = w.shape
    assert c % tile_c == 0, (c, tile_c)
    n_c = c // tile_c
    wt = w.reshape(k2, n_c, tile_c, m).transpose(1, 0, 2, 3)
    return wt.reshape(n_c, k2 * tile_c, m)


def untile_weights(wt: Array, kernel_size: int) -> Array:
    """Inverse of ``tile_weights``: (C//tc, K*K*tc, M) -> (K*K, C, M)."""
    k2 = kernel_size * kernel_size
    n_c, k2tc, m = wt.shape
    tc = k2tc // k2
    w = wt.reshape(n_c, k2, tc, m).transpose(1, 0, 2, 3)
    return w.reshape(k2, n_c * tc, m)


# ---------------------------------------------------------------------------
# Tile resolution (memoized — the chooser sweep runs once per layer shape)
#
# ISSUE 9: resolution consults the installed tuned-tile cache
# (``repro.tune``) FIRST — measured autotuner winners, keyed per
# (shape, objective, dtype, cores, platform) — and falls back to the
# analytic Sec. 3.2 chooser when the cache is cold, corrupt, or carries
# an entry incompatible with the layer.  Callers are unchanged: the
# dispatcher, Trainer, and serving engine read tuned tiles through the
# same ``resolve_tiles``/``warm_tile_cache`` they always called.
# ---------------------------------------------------------------------------

_TUNED_STATS = {"tuned_hits": 0, "analytic_resolves": 0,
                "tuned_incompatible": 0}


def reset_tuned_stats() -> None:
    """Zero the tuned-vs-analytic resolution counters (tests)."""
    for k in _TUNED_STATS:
        _TUNED_STATS[k] = 0


def _tuned_lookup(h: int, w: int, c: int, m: int, *, kernel_size: int,
                  stride: int, dilation: int, offset_bound: float,
                  objective: str, dtype: str | None,
                  cores: int) -> dict | None:
    """The installed tuned cache's entry for one resolution key, or
    None (no cache installed / key cold).  The lookup key includes the
    active lowering platform (``launch.platform``) so an interpret-mode
    wall-time winner is never served under Mosaic or the XLA reference
    lowering."""
    try:
        from repro.tune.cache import active_tile_cache
        cache = active_tile_cache()
        if cache is None:
            return None
        from repro.launch.platform import current_platform
        return cache.lookup(h=h, w=w, c=c, m=m, kernel_size=kernel_size,
                            stride=stride, dilation=dilation,
                            offset_bound=offset_bound,
                            objective=objective, dtype=dtype, cores=cores,
                            platform=current_platform())
    except Exception:  # noqa: BLE001 — a broken cache must not break dispatch
        return None


def _entry_tiles(entry: dict, c: int, m: int) -> tuple | None:
    """Validate one tuned entry against the layer it would configure:
    four positive ints whose channel tiles divide (C, M).  None on
    anything malformed or incompatible — the analytic fallback, counted
    in ``tile_cache_info``."""
    try:
        th, tw, tc, tm = (int(v) for v in entry["tiles"])
    except Exception:  # noqa: BLE001
        return None
    if min(th, tw, tc, tm) < 1 or c % tc != 0 or m % tm != 0:
        return None
    return th, tw, tc, tm


@functools.lru_cache(maxsize=256)
def resolve_tiles(h: int, w: int, c: int, m: int, *, kernel_size: int,
                  stride: int, dilation: int, offset_bound: float,
                  tile_h: int | None, tile_w: int | None,
                  tile_c: int | None, tile_m: int | None,
                  objective: str = "training",
                  dtype: str | None = None,
                  cores: int = 1
                  ) -> tuple[int, int, int, int]:
    """Fill unspecified tile sizes from the Sec. 3.2 chooser; explicit
    arguments win.  ``objective="training"`` (the ``deform_conv``
    default — the same resolved tiles serve the forward kernel and its
    custom-VJP backward) minimizes combined fwd+bwd zero-copy traffic
    under both VMEM working sets; the forward-only ``deform_sample``
    resolves with ``objective="forward"``.  ``dtype`` selects the
    element-width-aware budgets (``"int8"`` exploits the 4x band
    density of the quantized datapath); ``cores`` evaluates the
    training objective at the per-core backward traffic of the
    Megacore split.

    The installed tuned-tile cache (``repro.tune`` — measured
    autotuner winners, platform-keyed) is consulted before the analytic
    chooser; explicit tile arguments win over both, and a cold,
    corrupt, or layer-incompatible cache falls back to the chooser
    (recorded in :func:`tile_cache_info`).

    Memoized at both levels: this ``lru_cache`` keys the resolved call
    (so repeated un-jitted ``deform_conv`` calls skip even the chooser
    dispatch), and ``choose_kernel_tiles`` itself memoizes the full
    candidate sweep per layer shape (see ``tests/test_tiling.py``
    cache-hit coverage).  Installing a tuned cache or switching the
    platform clears this memo (``repro.tune.cache.install_tile_cache``
    / ``launch.platform.set_platform``).
    """
    from .ops import check_channel_tiles
    if None in (tile_h, tile_w, tile_c, tile_m):
        entry = _tuned_lookup(h, w, c, m, kernel_size=kernel_size,
                              stride=stride, dilation=dilation,
                              offset_bound=offset_bound,
                              objective=objective, dtype=dtype,
                              cores=cores)
        if entry is not None:
            tuned = _entry_tiles(entry, c, m)
            if tuned is not None:
                _TUNED_STATS["tuned_hits"] += 1
                tile_h = tile_h or tuned[0]
                tile_w = tile_w or tuned[1]
                tile_c = tile_c or tuned[2]
                tile_m = tile_m or tuned[3]
            else:
                _TUNED_STATS["tuned_incompatible"] += 1
                from repro.tune.cache import warn_once
                warn_once(
                    ("entry", h, w, c, m, objective, dtype, cores),
                    "tuned-tile cache entry for %dx%dx%d->%d is "
                    "malformed or incompatible with the layer "
                    "(tiles=%r); falling back to the analytic chooser "
                    "(warned once per key)", h, w, c, m,
                    entry.get("tiles"))
    if None in (tile_h, tile_w, tile_c, tile_m):
        _TUNED_STATS["analytic_resolves"] += 1
        shape = LayerShape(h=h, w=w, c_in=c, c_out=m,
                           kernel_size=kernel_size, stride=stride,
                           offset_bound=offset_bound)
        # The tuned cache keys "int8_chain" as its own quant entry
        # (ISSUE 10 satellite — chained winners never leak onto the
        # per-layer int8 path), but the analytic chooser's dtype-aware
        # budgets only know element widths: chain bands are int8.
        chooser_dtype = "int8" if dtype == "int8_chain" else dtype
        kt = choose_kernel_tiles(shape, dilation=dilation,
                                 objective=objective, dtype=chooser_dtype,
                                 cores=cores)
        tile_h = tile_h or kt.tile_h
        tile_w = tile_w or kt.tile_w
        tile_c = tile_c or kt.tile_c
        tile_m = tile_m or kt.tile_m
    check_channel_tiles(c, m, tile_c, tile_m)
    return tile_h, tile_w, tile_c, tile_m


def spec_tiles(spec: DCSpec, x: Array, offsets: Array,
               w: Array) -> tuple[int, int, int, int]:
    """Resolve (tile_h, tile_w, tile_c, tile_m) for one call — chooser
    defaults (combined fwd+bwd traffic), explicit spec values win, and
    spatial tiles are clamped to the output extent."""
    ho, wo = offsets.shape[1], offsets.shape[2]
    th, tw, tc, tm = resolve_tiles(
        x.shape[1], x.shape[2], x.shape[-1], w.shape[-1],
        kernel_size=spec.kernel_size, stride=spec.stride,
        dilation=spec.dilation, offset_bound=spec.offset_bound,
        tile_h=spec.tile_h, tile_w=spec.tile_w, tile_c=spec.tile_c,
        tile_m=spec.tile_m, cores=spec.cores)
    return min(th, ho), min(tw, wo), tc, tm


def _spatial_local_h(h: int, stride: int, spatial_shards: int, name: str,
                     *, kernel_size: int, dilation: int,
                     offset_bound: float) -> int:
    """Per-shard height a spatially sharded layer resolves tiles at.
    Applies the full split validation (ragged AND halo-thin) so an
    incompatible shard count fails at plan-warming time — engine init —
    not on the first sharded request."""
    if spatial_shards <= 1:
        return h
    from repro.core.tiling import spatial_halo_rows
    from repro.distributed.spatial import check_height_split
    try:
        check_height_split(
            h, shards=spatial_shards, stride=stride,
            min_rows=spatial_halo_rows(kernel_size=kernel_size,
                                       dilation=dilation,
                                       offset_bound=offset_bound))
    except ValueError as e:
        raise ValueError(f"layer {name!r}: {e}") from None
    return h // spatial_shards


def warm_tile_cache(layers, *, offset_bound: float, kernel_size: int = 3,
                    dilation: int = 1, objective: str = "forward",
                    dtype: str | None = None, cores: int = 1,
                    spatial_shards: int = 1,
                    ) -> dict[str, tuple[int, int, int, int]]:
    """Resolve (and memoize) the tile config for every named layer.

    ``layers`` maps a layer name to its dims
    ``{"h", "w", "c", "m", "stride"?}``.  This is the serving engine's
    per-bucket plan cache: each shape bucket calls it once at engine
    start, the Sec. 3.2 chooser sweep runs then (not on the first
    request), and every later ``deform_conv`` dispatch for the bucket
    hits the :func:`resolve_tiles` ``lru_cache``.  Returns
    ``{name: (tile_h, tile_w, tile_c, tile_m)}``.

    ``spatial_shards > 1`` warms the plans the ISSUE 10 spatial path
    will actually resolve: each shard sees the *local* height
    ``h // spatial_shards``, so warming at the global height would
    leave the per-shard plans cold (and the chooser sweep on the first
    request).  Raises the friendly split error per layer when a height
    does not divide.
    """
    resolved = {}
    for name, d in layers.items():
        stride = d.get("stride", 1)
        resolved[name] = resolve_tiles(
            _spatial_local_h(d["h"], stride, spatial_shards, name,
                             kernel_size=kernel_size, dilation=dilation,
                             offset_bound=offset_bound),
            d["w"], d["c"], d["m"], kernel_size=kernel_size,
            stride=stride, dilation=dilation,
            offset_bound=offset_bound, tile_h=None, tile_w=None,
            tile_c=None, tile_m=None, objective=objective, dtype=dtype,
            cores=cores)
    return resolved


def tile_source(h: int, w: int, c: int, m: int, *, kernel_size: int = 3,
                stride: int = 1, dilation: int = 1, offset_bound: float,
                objective: str = "forward", dtype: str | None = None,
                cores: int = 1, spatial_shards: int = 1) -> str:
    """Provenance of one layer's resolved tiles: ``"tuned"`` when the
    installed tuned cache would supply them (a valid platform-keyed
    entry exists), ``"analytic"`` otherwise — the serving engine
    records this per bucket plan so telemetry shows which plans came
    from the autotuner vs the Sec. 3.2 chooser.  ``spatial_shards``
    queries the per-shard (local-height) plan the spatial path uses."""
    h = _spatial_local_h(h, stride, spatial_shards, "tile_source",
                         kernel_size=kernel_size, dilation=dilation,
                         offset_bound=offset_bound)
    entry = _tuned_lookup(h, w, c, m, kernel_size=kernel_size,
                          stride=stride, dilation=dilation,
                          offset_bound=offset_bound, objective=objective,
                          dtype=dtype, cores=cores)
    if entry is not None and _entry_tiles(entry, c, m) is not None:
        return "tuned"
    return "analytic"


def tile_cache_info() -> dict:
    """Hit/miss counters of the memoized tile chooser — surfaced in the
    serving engine's telemetry so a bucket-miss storm (every request a
    fresh compile) is visible as a miss-rate spike.

    ISSUE 9 adds the tuned-cache resolution counters (``tuned_hits`` /
    ``analytic_resolves`` / ``tuned_incompatible`` — counted per
    memoization MISS, i.e. per fresh resolution) and the installed
    cache's status (``tuned_cache``: installed/entries/path/
    load_errors), so an analytic fallback — cold, corrupt, or
    incompatible — is visible, never silent."""
    ci = resolve_tiles.cache_info()
    info = {"hits": ci.hits, "misses": ci.misses, "size": ci.currsize}
    info.update(_TUNED_STATS)
    try:
        from repro.tune.cache import cache_info as _tuned_cache_info
        info["tuned_cache"] = _tuned_cache_info()
    except Exception:  # noqa: BLE001
        pass
    return info


# ---------------------------------------------------------------------------
# Input preparation
# ---------------------------------------------------------------------------

def pad_and_band(x: Array, *, kernel_size: int, stride: int, dilation: int,
                 offset_bound: float, tile_h: int,
                 ho: int) -> tuple[Array, int]:
    """Zero-pad x and slice it into overlapping row bands (legacy banded
    dataflow).

    Returns (bands, n_tiles): bands (N, n_tiles, band_h, w_pad, C).  The
    top/left zero padding of ``pad + halo`` (+1 bottom/right for the
    bilinear corner) makes every in-band corner index valid, so the
    kernel needs no masks — the bounded receptive field is the guarantee.
    """
    n, h, w, c = x.shape
    pad = dilation * (kernel_size // 2)
    hb, band_h = band_geometry(kernel_size=kernel_size, stride=stride,
                               dilation=dilation, offset_bound=offset_bound,
                               tile_h=tile_h)
    n_tiles = -(-ho // tile_h)

    p0 = pad + hb
    hp_needed = (n_tiles - 1) * tile_h * stride + band_h
    p1 = max(0, hp_needed - p0 - h)
    # Left pad aligns the kernel's band-local base (ox*S + hb); the +1 is
    # only needed on the right for the bilinear corner x0+1.
    xp = jnp.pad(x, ((0, 0), (p0, p1), (pad + hb, pad + hb + 1), (0, 0)))

    # Overlapping bands via a row gather (the halo duplication the paper
    # pays in BRAM; here it is an HBM-materialized copy produced by XLA —
    # exactly the redundant traffic the zero-copy dataflow removes).
    starts = jnp.arange(n_tiles) * (tile_h * stride)
    rows = starts[:, None] + jnp.arange(band_h)[None, :]     # (n_tiles, band_h)
    bands = jnp.take(xp, rows.reshape(-1), axis=1)
    bands = bands.reshape(n, n_tiles, band_h, xp.shape[2], c)
    return bands, n_tiles


def pad_zerocopy(x: Array, *, kernel_size: int, stride: int, dilation: int,
                 offset_bound: float, tile_h: int, tile_w: int,
                 ho: int, wo: int) -> Array:
    """Zero-pad x once for the zero-copy kernels — no band
    materialization; every (row-tile, width-tile) Eq. 6 band is a plain
    rectangular window of the result, DMA'd by the kernel itself."""
    n, h, w, c = x.shape
    pad = dilation * (kernel_size // 2)
    hb, band_h = band_geometry(kernel_size=kernel_size, stride=stride,
                               dilation=dilation, offset_bound=offset_bound,
                               tile_h=tile_h)
    _, band_w = band_geometry(kernel_size=kernel_size, stride=stride,
                              dilation=dilation, offset_bound=offset_bound,
                              tile_h=tile_w)
    band_w = staged_width(band_w)
    h_tiles = ho // tile_h
    w_tiles = wo // tile_w
    p0 = pad + hb
    pb = max(0, (h_tiles - 1) * tile_h * stride + band_h - p0 - h)
    pr = max(0, (w_tiles - 1) * tile_w * stride + band_w - p0 - w)
    return jnp.pad(x, ((0, 0), (p0, pb), (p0, pr), (0, 0)))


def zerocopy_inputs(spec: DCSpec, x: Array, offsets: Array, w: Array,
                    th: int, tw: int, tc: int,
                    extra: Array | None = None):
    """Shared input prep of the zero-copy forward and backward kernels:
    pad offsets (and ``extra``, the backward cotangent) to tile
    multiples, zero-pad the input per ``pad_zerocopy``, and block the
    weights.  One code path so the backward's un-pad slice can never
    disagree with the forward's padded geometry."""
    ho, wo = offsets.shape[1], offsets.shape[2]
    pad_h, pad_w = (-ho) % th, (-wo) % tw
    if pad_h or pad_w:
        offsets = jnp.pad(offsets, ((0, 0), (0, pad_h), (0, pad_w), (0, 0)))
        if extra is not None:
            extra = jnp.pad(extra, ((0, 0), (0, pad_h), (0, pad_w), (0, 0)))
    xp = pad_zerocopy(
        x, kernel_size=spec.kernel_size, stride=spec.stride,
        dilation=spec.dilation, offset_bound=spec.offset_bound,
        tile_h=th, tile_w=tw, ho=ho + pad_h, wo=wo + pad_w)
    w_tiled = tile_weights(w.astype(x.dtype), tc)
    if extra is not None:
        return xp, offsets, w_tiled, extra
    return xp, offsets, w_tiled


# ---------------------------------------------------------------------------
# Runners (shared by the single-device and the shard_map custom VJPs)
# ---------------------------------------------------------------------------

def reference_forward(x: Array, offsets: Array, w: Array, *,
                      kernel_size: int, stride: int, dilation: int,
                      offset_bound: float | None) -> Array:
    """Pure-XLA runner for the bounded forward — the degradation target
    of ``ops.deform_conv`` (PR 6): same Eq. 2 math and the same Eq. 5
    clamp as the zero-copy kernel, but gathers from HBM instead of
    staging bands.  Slower, never wrong — the bottom rung of the
    degradation ladder (docs/robustness.md)."""
    from .ref import deform_conv_fused_ref

    return deform_conv_fused_ref(
        x, offsets, w, kernel_size=kernel_size, stride=stride,
        dilation=dilation, offset_bound=offset_bound).astype(x.dtype)


def bounded_forward(spec: DCSpec, x: Array, offsets: Array,
                    w: Array) -> Array:
    ho, wo = offsets.shape[1], offsets.shape[2]
    c, m = x.shape[-1], w.shape[-1]

    if spec.dataflow == "banded":
        th = spec.tile_h or 8
        tc = spec.tile_c or c
        pad_h = (-ho) % th
        if pad_h:
            offsets = jnp.pad(offsets, ((0, 0), (0, pad_h), (0, 0), (0, 0)))
        bands, n_tiles = pad_and_band(
            x, kernel_size=spec.kernel_size, stride=spec.stride,
            dilation=spec.dilation, offset_bound=spec.offset_bound,
            tile_h=th, ho=ho + pad_h)
        w_tiles = tile_weights(w.astype(x.dtype), tc)
        y = deform_conv_fused_banded(
            bands, offsets, w_tiles, kernel_size=spec.kernel_size,
            stride=spec.stride, dilation=spec.dilation,
            offset_bound=spec.offset_bound, tile_h=th, tile_c=tc,
            tile_m=spec.tile_m, interpret=spec.interpret)
        return y[:, :ho]

    if spec.dataflow != "zero_copy":
        raise ValueError(
            f"unknown dataflow {spec.dataflow!r}; expected 'zero_copy' or "
            f"'banded'")
    th, tw, tc, tm = spec_tiles(spec, x, offsets, w)
    xp, offsets, w_tiled = zerocopy_inputs(spec, x, offsets, w, th, tw, tc)
    y = deform_conv_fused_zerocopy(
        xp, offsets, w_tiled, kernel_size=spec.kernel_size,
        stride=spec.stride, dilation=spec.dilation,
        offset_bound=spec.offset_bound, tile_h=th, tile_w=tw,
        tile_c=tc, tile_m=tm, interpret=spec.interpret)
    return y[:, :ho, :wo]


def bounded_backward(spec: DCSpec, x: Array, offsets: Array, w: Array,
                     gy: Array) -> tuple[Array, Array, Array]:
    """(d_input, d_offsets, d_weights) of one bounded call via the fused
    zero-copy backward kernel — shared by the single-device VJP and the
    per-shard body of the ``shard_map`` VJP."""
    n, h, w_, c = x.shape
    ho, wo = offsets.shape[1], offsets.shape[2]
    if offsets.shape[-1] != 2 * spec.kernel_size ** 2:
        raise NotImplementedError(
            "the fused backward kernel differentiates DCNv1 layers only "
            "(2*K*K offset channels); train a modulated (DCNv2) config "
            "through the pure-JAX path, use_kernel=False")
    th, tw, tc, _ = spec_tiles(spec, x, offsets, w)
    off_dtype = offsets.dtype
    dwf = spec.dw_flush_every_step
    if dwf is None:
        # Cadence resolution mirrors the tile resolution: the tuned
        # cache's measured winner (both flush cadences are bit-exact —
        # tests/test_deform_conv_grad.py parity), else the kernel
        # default.  Explicit spec values win, as for tiles.
        entry = _tuned_lookup(
            h, w_, c, w.shape[-1], kernel_size=spec.kernel_size,
            stride=spec.stride, dilation=spec.dilation,
            offset_bound=spec.offset_bound, objective="training",
            dtype=None, cores=spec.cores)
        if entry is not None:
            v = entry.get("dw_flush_every_step")
            dwf = v if isinstance(v, bool) else None
    xp, offsets, w_tiled, gy = zerocopy_inputs(spec, x, offsets, w,
                                               th, tw, tc, extra=gy)
    dxp, doff, dwt = deform_conv_bwd_zerocopy(
        xp, offsets, gy, w_tiled, kernel_size=spec.kernel_size,
        stride=spec.stride, dilation=spec.dilation,
        offset_bound=spec.offset_bound, tile_h=th, tile_w=tw, tile_c=tc,
        cores=spec.cores, interpret=spec.interpret,
        dw_flush_every_step=dwf)
    # Un-pad: pad_zerocopy put pad+hb zero rows/cols top-left.
    p0 = spec.dilation * (spec.kernel_size // 2) \
        + int(math.ceil(spec.offset_bound))
    dx = dxp[:, p0:p0 + h, p0:p0 + w_]
    doff = doff[:, :ho, :wo]
    dw = untile_weights(dwt, spec.kernel_size)
    return (dx.astype(x.dtype), doff.astype(off_dtype),
            dw.astype(w.dtype))


def int8_forward(x: Array, offsets: Array, w: Array, *,
                 kernel_size: int, stride: int, dilation: int,
                 offset_bound: float, tile_h: int | None,
                 tile_w: int | None, tile_c: int | None,
                 tile_m: int | None, x_scale: Array | None,
                 w_scale: Array | None, interpret: bool) -> Array:
    """int8 inference datapath: quantize (symmetric, per-tensor x /
    per-out-channel w), pad the int8 plane (0 -> 0, so padding and
    quantization commute), and run the fused int8->int32 zero-copy
    kernel with its per-M dequant epilogue.  Tiles resolve against the
    dtype-aware budgets (4x band density).  Training quantized models
    goes through ``repro.quant.qat`` (fake-quant over the fp32
    custom-VJP path), not here — ``jnp.round`` has no useful gradient.
    """
    from repro.quant.qtypes import compute_scale, quantize_values

    n, h, w_, c = x.shape
    ho, wo = offsets.shape[1], offsets.shape[2]
    m = w.shape[-1]
    th, tw, tc, tm = resolve_tiles(
        h, w_, c, m, kernel_size=kernel_size, stride=stride,
        dilation=dilation, offset_bound=offset_bound, tile_h=tile_h,
        tile_w=tile_w, tile_c=tile_c, tile_m=tile_m,
        objective="forward", dtype="int8")
    th, tw = min(th, ho), min(tw, wo)

    sx = compute_scale(x) if x_scale is None \
        else jnp.asarray(x_scale, jnp.float32)
    sw = compute_scale(w, axis=-1) if w_scale is None \
        else jnp.asarray(w_scale, jnp.float32).reshape(1, 1, m)
    xq = quantize_values(x, sx)
    wq = quantize_values(w, sw)

    pad_h, pad_w = (-ho) % th, (-wo) % tw
    if pad_h or pad_w:
        offsets = jnp.pad(offsets, ((0, 0), (0, pad_h), (0, pad_w), (0, 0)))
    xp = pad_zerocopy(
        xq, kernel_size=kernel_size, stride=stride, dilation=dilation,
        offset_bound=offset_bound, tile_h=th, tile_w=tw,
        ho=ho + pad_h, wo=wo + pad_w)
    w_tiled = tile_weights(wq, tc)
    scale = (sx * sw).reshape(1, m).astype(jnp.float32)
    y = deform_conv_fused_zerocopy_q(
        xp, offsets.astype(jnp.float32), w_tiled, scale,
        kernel_size=kernel_size, stride=stride, dilation=dilation,
        offset_bound=offset_bound, tile_h=th, tile_w=tw, tile_c=tc,
        tile_m=tm, interpret=interpret)
    return y[:, :ho, :wo].astype(x.dtype)


def chain_forward(x: Array, w: Array, w_offset: Array, b_offset: Array,
                  b_deform: Array | None, *, kernel_size: int, stride: int,
                  dilation: int, offset_bound: float,
                  x_scale, w_scale, w_offset_scale, y_scale,
                  tile_h: int | None, tile_w: int | None,
                  tile_c: int | None, tile_m: int | None,
                  emit: str, interpret: bool) -> Array:
    """int8 -> int8 chained DCL layer (inference datapath).

    x is either an int8 plane already on the ``x_scale`` grid (the
    previous chained layer's emission) or a fp32 plane quantized here
    (the chain head).  The offset conv is fused into the kernel
    (quantized weights ``w_offset``/``w_offset_scale``, fp32 dequant +
    ``b_offset``; 3*K*K outputs make the layer modulated, DCNv2), and
    the output is emitted int8 on the ``y_scale`` grid with the
    per-channel requant ``s_x * s_w[m] / s_y`` and the deform bias
    folded as ``b[m] / s_y`` (``emit="fp32"`` is the chain
    tail: plain dequant + bias).  The chained layer's activations touch
    HBM only as int8 — no fp32 round-trip and no offsets in HBM at all.
    """
    from repro.quant.qtypes import compute_scale, quantize_values

    n, h, w_in, c = x.shape
    m = w.shape[-1]
    n_off = w_offset.shape[-1]          # 2*K*K, or 3*K*K modulated
    ho, wo = out_hw(h, w_in, kernel_size=kernel_size, stride=stride,
                    dilation=dilation)
    if tile_c is not None and tile_c != c:
        raise ValueError(
            f"tile_c={tile_c} is incompatible with chaining: the fused "
            f"offset-conv stage needs the whole channel extent staged "
            f"per band (tile_c == C = {c}), since the offsets must be "
            f"complete before the first bilinear sample consumes them — "
            f"pass tile_c=None (or C) for chained layers")
    th, tw, _, tm = resolve_tiles(
        h, w_in, c, m, kernel_size=kernel_size, stride=stride,
        dilation=dilation, offset_bound=offset_bound, tile_h=tile_h,
        tile_w=tile_w, tile_c=c, tile_m=tile_m,
        objective="forward", dtype="int8_chain")
    th, tw = min(th, ho), min(tw, wo)
    # The chooser's VMEM feasibility was evaluated at its own free
    # tile_c; chaining pins tile_c = C, so re-check the working set the
    # kernel will actually allocate (single-buffer full-C int8 band +
    # offset-conv stage) and shrink the spatial tiles until it fits.
    from repro.core.tiling import (TileConfig, VMEM_LIMIT_BYTES,
                                   zerocopy_vmem_bytes)

    def _chain_vmem(th_, tw_):
        return zerocopy_vmem_bytes(
            LayerShape(h=h, w=w_in, c_in=c, c_out=m,
                       kernel_size=kernel_size, stride=stride,
                       offset_bound=offset_bound),
            TileConfig(th_, tw_, c, tm), dilation=dilation,
            bytes_per_elem=1, aux_bytes_per_elem=4, fuse_offsets=True,
            offset_channels=n_off)
    while _chain_vmem(th, tw) > VMEM_LIMIT_BYTES and (th > 1 or tw > 1):
        if tw > 1:
            tw = max(1, tw // 2)
        else:
            th = max(1, th // 2)

    sx = jnp.asarray(x_scale, jnp.float32)
    sw = compute_scale(w, axis=-1) if w_scale is None \
        else jnp.asarray(w_scale, jnp.float32).reshape(1, 1, m)
    swo = compute_scale(w_offset, axis=-1) if w_offset_scale is None \
        else jnp.asarray(w_offset_scale, jnp.float32).reshape(1, 1, n_off)
    xq = x if x.dtype == jnp.int8 else quantize_values(x, sx)
    wq = quantize_values(w, sw)
    woq = quantize_values(w_offset, swo)

    pad_h, pad_w = (-ho) % th, (-wo) % tw
    xp = pad_zerocopy(
        xq, kernel_size=kernel_size, stride=stride, dilation=dilation,
        offset_bound=offset_bound, tile_h=th, tile_w=tw,
        ho=ho + pad_h, wo=wo + pad_w)
    w_tiled = tile_weights(wq, c)
    wo_tiled = tile_weights(woq, c)
    off_scale = (sx * swo).reshape(1, n_off).astype(jnp.float32)
    off_bias = jnp.asarray(b_offset, jnp.float32).reshape(1, n_off)
    bias = jnp.zeros((m,), jnp.float32) if b_deform is None \
        else jnp.asarray(b_deform, jnp.float32)
    if emit == "int8":
        sy = jnp.asarray(y_scale, jnp.float32)
        out_scale = (sx * sw / sy).reshape(1, m).astype(jnp.float32)
        out_bias = (bias / sy).reshape(1, m)
    else:
        out_scale = (sx * sw).reshape(1, m).astype(jnp.float32)
        out_bias = bias.reshape(1, m)
    y = deform_conv_fused_zerocopy_chain(
        xp, w_tiled, wo_tiled, off_scale, off_bias, out_scale, out_bias,
        kernel_size=kernel_size, stride=stride, dilation=dilation,
        offset_bound=offset_bound, tile_h=th, tile_w=tw, tile_m=tm,
        emit=emit, ho=ho + pad_h, wo=wo + pad_w, interpret=interpret)
    return y[:, :ho, :wo]
