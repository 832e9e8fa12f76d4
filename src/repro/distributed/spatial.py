"""Spatial (height) sharding of the bounded DCL kernels — bounded halo
exchange over a mesh axis (ISSUE 10).

Batch data-parallelism (``ops.resolve_batch_shard``) cannot reduce the
latency of *one* megapixel image; this module shards the height grid
axis instead.  The paper's Eq. 5 trained offset bound is what makes
that cheap: the same bound that keeps every gather inside the Eq. 6
band statically bounds the inter-device dependency to

    halo = dilation*(K//2) + ceil(B) + 1        (= B + ceil(K/2) rows
                                                 for dilation=1, odd K)

rows of the neighbor shard (``core.tiling.spatial_halo_rows`` is the
single source of that algebra), so spatial parallelism is exactly one
``lax.ppermute`` up/down halo-exchange pair per layer — the
bounded-access locality argument of Huang et al. / CoDeNet transplanted
from on-chip buffers to the mesh.

Geometry.  The unsharded zero-copy path pads the input top/left by
``p0 = dilation*(K//2) + ceil(B)`` zero rows (``plan.pad_zerocopy``);
output row ``t`` then reads padded rows ``[t*s, t*s + band_extent(1))``
= original rows ``[t*s - p0, t*s + p0 + 1]``.  With the height split
``H % (stride*shards) == 0``, shard ``i`` owns output rows
``[i*ho_loc, (i+1)*ho_loc)`` and needs original rows
``[i*h_loc - p0, (i+1)*h_loc - s + p0 + 1]`` — at most ``halo = p0+1``
rows beyond its own block on either side.  After the exchange the
shard trims its halo-extended block to the exact local analogue of the
global padded slab (``_shard_slab``) and runs the *unmodified*
zero-copy kernels on it, so per-shard outputs equal the corresponding
global output rows bit-for-bit (same tiles => same arithmetic).  The
non-cyclic ``ppermute`` delivers zeros at the edge shards — exactly
the zero padding the global path applies, for free.

Backward mirrors it: the fused backward kernel produces ``d_input``
over the halo-extended extent; the rows that belong to the neighbors
are ppermuted back (reverse directions) and added into their local
``d_input``, ``d_weights`` is psummed over the spatial (and any
composed batch) mesh axes, and ``d_offsets`` stays local.  The
``custom_vjp`` wraps the shard_maps — never the other way round — so
gradient correctness does not depend on shard_map transpose rules
(same structure as ``ops._deform_conv_sharded``).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.tiling import spatial_halo_rows, staged_width
from repro.kernels import plan as _plan
from repro.kernels.band_pipeline import band_geometry
from repro.kernels.deform_conv_bwd import deform_conv_bwd_zerocopy
from repro.kernels.deform_conv_fused import deform_conv_fused_zerocopy
from repro.kernels.deform_conv_q import deform_conv_fused_zerocopy_q
from .sharding import current_rules

Array = jax.Array


def halo_rows(*, kernel_size: int, dilation: int = 1,
              offset_bound: float) -> int:
    """Rows exchanged with each height-shard neighbor — delegates to
    ``core.tiling.spatial_halo_rows`` so the runtime exchange and the
    HBM/ICI traffic model can never disagree."""
    return spatial_halo_rows(kernel_size=kernel_size, dilation=dilation,
                             offset_bound=offset_bound)


def check_height_split(h: int, *, shards: int, stride: int = 1,
                       min_rows: int | None = None) -> None:
    """Reject height splits the spatial shard_map cannot serve — a clear
    ``ValueError`` naming the sizes at the public entry (a la
    ``ops.check_batch_split``) instead of a deep shard_map shape error.

    ``min_rows`` (the halo extent, when the caller knows it) addition-
    ally rejects shards thinner than their own halo — the exchange
    slices ``x[:, -halo:]`` need ``H/shards >= halo`` rows per shard.
    """
    if shards < 1:
        raise ValueError(f"spatial shards={shards} must be >= 1")
    if h % (stride * shards) != 0:
        raise ValueError(
            f"spatial shards={shards} does not evenly divide height "
            f"H={h} at stride={stride}; the spatial shard_map needs "
            f"equal per-device row blocks (H % (stride*shards) == 0) — "
            f"pad the input height or pick a shard count dividing "
            f"{h // stride if h % stride == 0 else h}")
    if min_rows is not None and shards > 1 and h // shards < min_rows:
        raise ValueError(
            f"spatial shards={shards} leaves only {h // shards} rows "
            f"per shard, thinner than the {min_rows}-row halo the "
            f"bounded exchange needs — use fewer shards (or a smaller "
            f"offset bound)")


def spatial_mesh_axes() -> tuple[Mesh, str, int] | None:
    """Mesh axis the 'spatial' logical axis maps to under the *active*
    rules: ``(mesh, axis_name, size)``, or None when no mesh is active
    or the rules map 'spatial' to nothing.

    Unlike ``sharding.batch_mesh_axes`` this keeps size-1 axes: a
    1-shard spatial run still routes through the halo-exchange path
    (empty ``ppermute`` perm => zero halos == the global zero padding),
    which is exactly what the bit-identity parity tests exercise.
    Multiple mapped mesh axes raise — the ``ppermute`` ring needs one
    well-ordered axis.
    """
    ctx = current_rules()
    if ctx is None or ctx[1] is None:
        return None
    rules, mesh = ctx
    target = rules.get("spatial")
    if target is None:
        return None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = tuple(ax for ax in ((target,) if isinstance(target, str)
                               else tuple(target))
                 if ax in sizes)
    if not axes:
        return None
    if len(axes) > 1:
        raise ValueError(
            f"the 'spatial' logical axis maps to {axes} under the "
            f"active rules; the halo-exchange ppermute needs exactly "
            f"one mesh axis — map 'spatial' to a single axis")
    return mesh, axes[0], sizes[axes[0]]


@dataclasses.dataclass(frozen=True)
class SpatialSpec:
    """Hashable mesh context of one height-sharded deform_conv call.

    ``batch_axes`` composes batch data-parallelism into the same
    shard_map (spatial x data 2-D mesh): dim 0 of every activation is
    sharded over them while dim 1 (height) rides ``axis``.
    """
    mesh: Mesh
    axis: str
    shards: int
    batch_axes: tuple[str, ...] = ()

    def pspec(self, rank: int) -> P:
        """PartitionSpec sharding dim 0 over the batch axes (if any)
        and dim 1 (height) over the spatial axis."""
        b = self.batch_axes if self.batch_axes else None
        return P(b, self.axis, *([None] * (rank - 2)))

    @property
    def psum_axes(self) -> tuple[str, ...]:
        return (self.axis, *self.batch_axes)


def resolve_spatial_shard(h: int, *, shard_spatial: bool | None = None,
                          stride: int = 1, kernel_size: int = 3,
                          dilation: int = 1, offset_bound: float = 0.0,
                          batch_axes: tuple[str, ...] = ()
                          ) -> SpatialSpec | None:
    """Decide whether to shard the height axis over the active mesh.

    * ``shard_spatial=None``/``False``: never — spatial sharding is
      strictly opt-in (unlike ``shard_batch``'s auto mode) because the
      default rules map 'spatial' to the 'model' axis, and silently
      height-sharding every bounded call under a model-parallel mesh
      would change the layout of existing callers.
    * ``shard_spatial=True``: require it — no active mesh mapping
      'spatial', a ragged height split, or shards thinner than the
      halo raise a ``ValueError`` naming the sizes.
    """
    if not shard_spatial:
        return None
    got = spatial_mesh_axes()
    if got is None:
        raise ValueError(
            "shard_spatial=True but no mesh maps the 'spatial' logical "
            "axis — activate one with distributed.sharding."
            "use_rules(mesh=...) whose rules map 'spatial' to a mesh "
            "axis (DEFAULT_RULES maps it to 'model')")
    mesh, axis, size = got
    if axis in batch_axes:
        raise ValueError(
            f"the 'spatial' mesh axis {axis!r} is already used by the "
            f"batch shard {batch_axes} — a mesh axis may carry one "
            f"logical axis per call; use a 2-D mesh (e.g. ('data', "
            f"'model')) so batch and height shard different axes")
    halo = halo_rows(kernel_size=kernel_size, dilation=dilation,
                     offset_bound=offset_bound)
    check_height_split(h, shards=size, stride=stride, min_rows=halo)
    return SpatialSpec(mesh=mesh, axis=axis, shards=size,
                       batch_axes=tuple(batch_axes))


# ---------------------------------------------------------------------------
# Shard bodies
# ---------------------------------------------------------------------------

def exchange_halo(x: Array, *, axis_name: str, shards: int,
                  halo: int) -> Array:
    """The one up/down halo-exchange pair: concatenate each shard's
    height block with ``halo`` edge rows from both neighbors.  The
    non-cyclic ``ppermute`` (idiom of ``distributed.pipeline``) leaves
    the edge shards' missing neighbor as zeros — exactly the zero
    padding the unsharded path applies there."""
    down = [(i, i + 1) for i in range(shards - 1)]
    up = [(i + 1, i) for i in range(shards - 1)]
    top = jax.lax.ppermute(x[:, -halo:], axis_name, down)
    bot = jax.lax.ppermute(x[:, :halo], axis_name, up)
    return jnp.concatenate([top, x, bot], axis=1)


def _shard_slab(x_ext: Array, *, kernel_size: int, stride: int,
                dilation: int, offset_bound: float, tile_h: int,
                tile_w: int, ho: int, wo: int) -> Array:
    """Trim one halo-extended shard block to the local analogue of the
    global ``plan.pad_zerocopy`` slab.

    Global slab row ``u`` is original row ``u - p0``; local slab row
    ``j`` must be original row ``i*h_loc - p0 + j``, and ``x_ext`` row
    0 is original row ``i*h_loc - halo`` — so the slab starts at
    ``x_ext`` row ``halo - p0`` (= 1, by construction).  Width gets the
    same left-``p0``/right zero padding as ``pad_zerocopy`` (the width
    axis is not sharded), and the bottom is zero-padded out to the
    tile-rounded band extent (those rows feed only the padded output
    rows that are sliced away)."""
    n, h_ext, w_, c = x_ext.shape
    pad = dilation * (kernel_size // 2)
    hb, band_h = band_geometry(kernel_size=kernel_size, stride=stride,
                               dilation=dilation, offset_bound=offset_bound,
                               tile_h=tile_h)
    _, band_w = band_geometry(kernel_size=kernel_size, stride=stride,
                              dilation=dilation, offset_bound=offset_bound,
                              tile_h=tile_w)
    band_w = staged_width(band_w)
    p0 = pad + hb
    halo = p0 + 1
    h_tiles = ho // tile_h
    w_tiles = wo // tile_w
    top = halo - p0                                    # = 1
    need_h = (h_tiles - 1) * tile_h * stride + band_h
    pb = max(0, need_h - (h_ext - top))
    pr = max(0, (w_tiles - 1) * tile_w * stride + band_w - p0 - w_)
    slab = x_ext[:, top:]
    return jnp.pad(slab, ((0, 0), (0, pb), (p0, pr), (0, 0)))


def _spatial_forward(spec: _plan.DCSpec, sspec: SpatialSpec, x: Array,
                     offsets: Array, w: Array) -> Array:
    """Per-shard forward body: halo exchange, slab trim, then the
    unmodified zero-copy kernel on the local rows.  Tiles resolve at
    the LOCAL shard shape (``x`` here is the per-device block), so the
    ``resolve_tiles`` memo and the tuned-tile cache key by shard-local
    height — tuned plans never leak across shard counts."""
    ho, wo = offsets.shape[1], offsets.shape[2]
    th, tw, tc, tm = _plan.spec_tiles(spec, x, offsets, w)
    pad_h, pad_w = (-ho) % th, (-wo) % tw
    if pad_h or pad_w:
        offsets = jnp.pad(offsets, ((0, 0), (0, pad_h), (0, pad_w), (0, 0)))
    halo = halo_rows(kernel_size=spec.kernel_size, dilation=spec.dilation,
                     offset_bound=spec.offset_bound)
    x_ext = exchange_halo(x, axis_name=sspec.axis, shards=sspec.shards,
                          halo=halo)
    slab = _shard_slab(x_ext, kernel_size=spec.kernel_size,
                       stride=spec.stride, dilation=spec.dilation,
                       offset_bound=spec.offset_bound, tile_h=th,
                       tile_w=tw, ho=ho + pad_h, wo=wo + pad_w)
    w_tiled = _plan.tile_weights(w.astype(x.dtype), tc)
    y = deform_conv_fused_zerocopy(
        slab, offsets, w_tiled, kernel_size=spec.kernel_size,
        stride=spec.stride, dilation=spec.dilation,
        offset_bound=spec.offset_bound, tile_h=th, tile_w=tw,
        tile_c=tc, tile_m=tm, interpret=spec.interpret)
    return y[:, :ho, :wo]


def _spatial_backward(spec: _plan.DCSpec, sspec: SpatialSpec, x: Array,
                      offsets: Array, w: Array, gy: Array
                      ) -> tuple[Array, Array, Array]:
    """Per-shard backward body.  The fused backward kernel writes
    ``d_input`` over the halo-extended slab; the ``p0`` rows above the
    local block belong to the previous shard and the ``p0+1`` rows
    below to the next — those halo-gradient rows are ppermuted back
    (reverse directions of the forward exchange) and ADDED into the
    neighbors' local ``d_input``; edge shards receive zeros (a no-op
    add), matching the global path's discarded zero-pad gradients.
    ``d_weights`` is psummed over the spatial + batch axes."""
    n, h_loc, w_in, c = x.shape
    ho, wo = offsets.shape[1], offsets.shape[2]
    th, tw, tc, _ = _plan.spec_tiles(spec, x, offsets, w)
    off_dtype = offsets.dtype
    dwf = spec.dw_flush_every_step
    if dwf is None:
        entry = _plan._tuned_lookup(
            h_loc, w_in, c, w.shape[-1], kernel_size=spec.kernel_size,
            stride=spec.stride, dilation=spec.dilation,
            offset_bound=spec.offset_bound, objective="training",
            dtype=None, cores=spec.cores)
        if entry is not None:
            v = entry.get("dw_flush_every_step")
            dwf = v if isinstance(v, bool) else None
    pad_h, pad_w = (-ho) % th, (-wo) % tw
    if pad_h or pad_w:
        offsets = jnp.pad(offsets, ((0, 0), (0, pad_h), (0, pad_w), (0, 0)))
        gy = jnp.pad(gy, ((0, 0), (0, pad_h), (0, pad_w), (0, 0)))
    halo = halo_rows(kernel_size=spec.kernel_size, dilation=spec.dilation,
                     offset_bound=spec.offset_bound)
    p0 = halo - 1
    x_ext = exchange_halo(x, axis_name=sspec.axis, shards=sspec.shards,
                          halo=halo)
    slab = _shard_slab(x_ext, kernel_size=spec.kernel_size,
                       stride=spec.stride, dilation=spec.dilation,
                       offset_bound=spec.offset_bound, tile_h=th,
                       tile_w=tw, ho=ho + pad_h, wo=wo + pad_w)
    w_tiled = _plan.tile_weights(w.astype(x.dtype), tc)
    dxp, doff, dwt = deform_conv_bwd_zerocopy(
        slab, offsets, gy, w_tiled, kernel_size=spec.kernel_size,
        stride=spec.stride, dilation=spec.dilation,
        offset_bound=spec.offset_bound, tile_h=th, tile_w=tw, tile_c=tc,
        cores=spec.cores, interpret=spec.interpret,
        dw_flush_every_step=dwf)
    # Un-pad width (the width axis is unsharded, same as the global
    # path), keep the full halo-extended row extent for the exchange.
    dxe = dxp[:, :, p0:p0 + w_in]
    dx = dxe[:, p0:p0 + h_loc]
    if sspec.shards > 1:
        # Rows [0, p0) are grads of the previous shard's last p0 rows;
        # rows [p0+h_loc, p0+h_loc+p0+1) of the next shard's first ones.
        to_prev = [(i, i - 1) for i in range(1, sspec.shards)]
        to_next = [(i, i + 1) for i in range(sspec.shards - 1)]
        if p0 > 0:
            from_next = jax.lax.ppermute(dxe[:, :p0], sspec.axis, to_prev)
            dx = dx.at[:, h_loc - p0:].add(from_next)
        from_prev = jax.lax.ppermute(
            dxe[:, p0 + h_loc:p0 + h_loc + p0 + 1], sspec.axis, to_next)
        dx = dx.at[:, :p0 + 1].add(from_prev)
    doff = doff[:, :ho, :wo]
    dw = _plan.untile_weights(dwt, spec.kernel_size)
    dw = jax.lax.psum(dw, sspec.psum_axes)
    return (dx.astype(x.dtype), doff.astype(off_dtype), dw.astype(w.dtype))


# ---------------------------------------------------------------------------
# custom_vjp over shard_map (fp32) + the plain-shard_map int8 path
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def deform_conv_spatial(spec: _plan.DCSpec, sspec: SpatialSpec, x: Array,
                        offsets: Array, w: Array) -> Array:
    """Height-sharded bounded fp32 deform_conv: shard_map over the
    spatial (x optional batch) mesh axes with one halo exchange per
    call; differentiable via the fused backward kernel + halo-gradient
    return (see module docstring)."""
    ps = sspec.pspec(4)
    fn = jax.shard_map(functools.partial(_spatial_forward, spec, sspec),
                       mesh=sspec.mesh,
                       in_specs=(ps, ps, P(None, None, None)),
                       out_specs=ps, check_vma=False)
    return fn(x, offsets, w)


def _deform_conv_spatial_fwd(spec, sspec, x, offsets, w):
    return deform_conv_spatial(spec, sspec, x, offsets, w), (x, offsets, w)


def _deform_conv_spatial_bwd(spec, sspec, res, gy):
    x, offsets, w = res
    ps = sspec.pspec(4)
    rep_w = P(None, None, None)
    fn = jax.shard_map(functools.partial(_spatial_backward, spec, sspec),
                       mesh=sspec.mesh,
                       in_specs=(ps, ps, rep_w, ps),
                       out_specs=(ps, ps, rep_w), check_vma=False)
    return fn(x, offsets, w, gy)


deform_conv_spatial.defvjp(_deform_conv_spatial_fwd,
                           _deform_conv_spatial_bwd)


def spatial_int8_forward(x: Array, offsets: Array, w: Array, *,
                         kernel_size: int, stride: int, dilation: int,
                         offset_bound: float, tile_h: int | None,
                         tile_w: int | None, tile_c: int | None,
                         tile_m: int | None, x_scale: Array | None,
                         w_scale: Array | None, interpret: bool,
                         sspec: SpatialSpec) -> Array:
    """Height-sharded int8 inference datapath (no VJP — quantized
    inference only, like ``plan.int8_forward``).

    The quantization scales are hoisted OUTSIDE the shard_map: a
    per-shard dynamic absmax would give each shard its own int8 grid
    and break parity with the unsharded kernel, so the global plane is
    quantized once (calibrated scales, or one global absmax) and the
    halo exchange carries int8 rows — 4x cheaper on the wire, and
    exactly the bytes the traffic model charges.  int8 accumulation is
    exact (s8 x s8 -> s32), so per-shard outputs match the unsharded
    kernel bit-for-bit regardless of the locally resolved tiles."""
    from repro.quant.qtypes import compute_scale, quantize_values

    m = w.shape[-1]
    sx = compute_scale(x) if x_scale is None \
        else jnp.asarray(x_scale, jnp.float32)
    sw = compute_scale(w, axis=-1) if w_scale is None \
        else jnp.asarray(w_scale, jnp.float32).reshape(1, 1, m)
    xq = quantize_values(x, sx)
    wq = quantize_values(w, sw)
    scale = (sx * sw).reshape(1, m).astype(jnp.float32)

    def body(xq, offsets, wq, scale):
        ho, wo = offsets.shape[1], offsets.shape[2]
        h_loc, w_in, c = xq.shape[1], xq.shape[2], xq.shape[3]
        th, tw, tc, tm = _plan.resolve_tiles(
            h_loc, w_in, c, m, kernel_size=kernel_size, stride=stride,
            dilation=dilation, offset_bound=offset_bound, tile_h=tile_h,
            tile_w=tile_w, tile_c=tile_c, tile_m=tile_m,
            objective="forward", dtype="int8")
        th, tw = min(th, ho), min(tw, wo)
        pad_h, pad_w = (-ho) % th, (-wo) % tw
        offs = offsets
        if pad_h or pad_w:
            offs = jnp.pad(offs, ((0, 0), (0, pad_h), (0, pad_w), (0, 0)))
        halo = halo_rows(kernel_size=kernel_size, dilation=dilation,
                         offset_bound=offset_bound)
        x_ext = exchange_halo(xq, axis_name=sspec.axis,
                              shards=sspec.shards, halo=halo)
        slab = _shard_slab(x_ext, kernel_size=kernel_size, stride=stride,
                           dilation=dilation, offset_bound=offset_bound,
                           tile_h=th, tile_w=tw, ho=ho + pad_h,
                           wo=wo + pad_w)
        w_tiled = _plan.tile_weights(wq, tc)
        y = deform_conv_fused_zerocopy_q(
            slab, offs.astype(jnp.float32), w_tiled, scale,
            kernel_size=kernel_size, stride=stride, dilation=dilation,
            offset_bound=offset_bound, tile_h=th, tile_w=tw, tile_c=tc,
            tile_m=tm, interpret=interpret)
        return y[:, :ho, :wo]

    ps = sspec.pspec(4)
    fn = jax.shard_map(body, mesh=sspec.mesh,
                       in_specs=(ps, ps, P(None, None, None), P(None, None)),
                       out_specs=ps, check_vma=False)
    return fn(xq, offsets, wq, scale).astype(x.dtype)
