"""Public entry points for the Pallas kernels (checking, VJPs, dispatch).

The dispatch mirrors the paper's co-design argument:

* ``offset_bound`` given (the Eq. 5-trained model) -> the Pallas
  bounded-halo kernels: static HBM->VMEM bands, no irregular HBM access.
* ``offset_bound`` None (the lambda=0 baseline) -> the pure-XLA gather
  path of ``repro.core.deform_conv`` — dynamic gathers from HBM, exactly
  the "irregular DRAM access" regime the paper measures against.

Every bounded kernel is emitted by the unified band-pipeline emitter
(``kernels.band_pipeline`` — ``BandSpec``/``DCLPlan`` + the
double-buffered ``make_async_copy`` band stager); the plan building and
the runner bodies live in ``kernels.plan`` (see ``docs/kernels.md``).
This module is the thin public surface: argument checking, mesh/shard
resolution, the ``jax.custom_vjp`` wiring, and the precision dispatch.

Bounded kernels support two dataflows (``dataflow=``):

* ``"zero_copy"`` (default) — the input is zero-padded once and handed
  whole to the kernel in ``ANY``/HBM memory space; the kernel issues
  double-buffered ``make_async_copy`` DMAs per Eq. 6 (row, width) band.
  Nothing is duplicated in HBM and VMEM is bounded independent of image
  size.  Tile sizes default to the Sec. 3.2 chooser
  (``repro.core.tiling.choose_kernel_tiles``); pass explicit tiles to
  override.
* ``"banded"`` (legacy) — ``plan.pad_and_band`` materializes overlapping
  full-width row bands in HBM via an XLA gather (a
  ``band_h/(tile_h*stride)`` ~ 2-3x duplication of the input) before
  the kernel runs.  Kept as the parity baseline; see EXPERIMENTS.md
  §Perf for the modeled traffic difference.

``interpret`` defaults to the lowering platform of ``launch.platform``:
Mosaic on a TPU backend, Pallas interpret mode elsewhere (the CPU
tests).

The bounded ``deform_conv`` path is differentiable: it is wrapped in a
``jax.custom_vjp`` whose backward is the fused zero-copy kernel of
``deform_conv_bwd.py`` (d_input, d_offsets, d_weights in one band-DMA
pass), so Eq. 5-bounded *training* also runs the zero-copy dataflow —
never an XLA gather/scatter against HBM.

``deform_conv(precision="int8")`` dispatches the quantized inference
datapath: symmetric int8 band DMA + int8 MXU contraction with int32
accumulation, fp32 bilinear coefficients, fused per-out-channel dequant
epilogue — tiles resolved against the dtype-aware budgets (4x Eq. 6
band density).  Scales come from ``repro.quant`` calibration or dynamic
absmax.

``deform_conv_chain`` is the int8 layer-chaining entry (ROADMAP int8
follow-ups, both): the offset conv is fused into the kernel (an int8
MXU stage over the already-staged Eq. 6 band — no separate fp32 offset
pass, no offsets in HBM) and the output is emitted int8 on the *next*
layer's activation grid via a fused per-channel requant, so
back-to-back DCLs chain int8 -> int8 with no fp32 HBM round-trip
between layers (``models.layers.dcl_apply(quant="int8_chain")``).

Parallel training (PR 4), two composable levels:

* ``cores=`` splits the *backward* kernel's batch grid axis into
  per-core shards (Megacore ``parallel`` dimension semantics; see
  ``deform_conv_bwd.py``) with a cheap per-core ``d_weights`` reduce
  epilogue.
* When a mesh is active (``distributed.sharding.use_rules(mesh=...)``)
  and the 'batch' logical axis maps to real mesh axes, the bounded
  fp32 path wraps itself in ``shard_map`` over those axes: each device
  runs the full zero-copy fwd/bwd kernels on its batch shard and the
  custom VJP psums ``d_weights`` across the data axes — data-parallel
  DCL training never falls back to GSPMD partitioning the kernel
  internals (which replicates / re-gathers).  ``shard_batch`` selects
  the mode: None (auto: shard when the mesh divides the batch),
  True (require sharding — non-divisible batches raise), False (never).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import logging

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.deform_conv import DCLConfig, sample_patches
from repro.distributed.sharding import batch_mesh_axes
from repro.distributed import spatial as _spatial
from . import plan as _plan
from .deform_sample import deform_sample_banded, deform_sample_zerocopy
from .matmul import matmul  # re-export  # noqa: F401
from .plan import (DCSpec as _DCSpec, chain_forward, int8_forward,
                   resolve_tiles, tile_weights, untile_weights)

Array = jax.Array

DEFAULT_DATAFLOW = "zero_copy"

# Back-compat aliases (tests and older callers import the underscored
# names from here).
_pad_and_band = _plan.pad_and_band
_pad_zerocopy = _plan.pad_zerocopy
_zerocopy_inputs = _plan.zerocopy_inputs
_bounded_forward = _plan.bounded_forward
_bounded_backward = _plan.bounded_backward
_spec_tiles = _plan.spec_tiles


def default_interpret() -> bool:
    """Whether the Pallas kernels run in interpret mode by default —
    now a view of the process-global lowering platform
    (``launch.platform``): Mosaic (False) only under platform 'tpu'."""
    from repro.launch.platform import current_platform
    return current_platform() != "tpu"


# ---------------------------------------------------------------------------
# Graceful degradation (PR 6).
#
# Argument validation (bad tiles, missing scales, unknown modes) is
# hoisted into the un-jitted public wrappers and always RAISES — a wrong
# call is a caller bug, and the friendly ValueErrors are part of the
# API.  Failures past validation — plan resolution, the emitter, kernel
# lowering, or an injected dispatch fault — raise too by default: a
# silent switch to the XLA reference would hide a kernel that does not
# run on the device.  Callers that prefer to be served anyway opt in
# with ``degradation_scope(True)``: the wrappers then fall back to the
# reference path (``ref.deform_conv_fused_ref`` / the fake-quant
# oracles of ``repro.quant.qat``) with exactly one warning per
# (entry, precision) on the ``repro.resilience`` logger.  The ladder:
# int8_chain -> int8 -> fp32 kernel -> XLA reference
# (docs/robustness.md); each rung's fallback is the reference form of
# the SAME arithmetic, so degraded outputs stay parity-close.
#
# ``set_dispatch_hook`` installs a callable consulted (with a context
# dict) before each bounded dispatch — the chaos harness's injection
# seam.  It lives in the un-jitted wrappers on purpose: inside the
# jitted impl it would fire once per trace, then never again.
# ---------------------------------------------------------------------------

_log = logging.getLogger("repro.resilience")

_dispatch_hook = None
_degrade_enabled = False
_FALLBACK_WARNED: set = set()


def set_dispatch_hook(hook):
    """Install (or clear, with None) the dispatcher hook; returns the
    previous hook.  Called as ``hook(context_dict)`` before every
    bounded kernel dispatch; raising aborts the kernel path and
    triggers the degradation fallback.

    ISSUE 8: a hook may RETURN a ``finish(out=None, error=None)``
    callable, which the wrapper invokes after the kernel call resolves
    (success or failure) — the measurement seam
    ``repro.obs.DispatchRecorder`` closes its per-dispatch timing
    through.  A None return (the chaos harness) keeps the old
    fire-and-forget contract."""
    global _dispatch_hook
    prev, _dispatch_hook = _dispatch_hook, hook
    return prev


def get_dispatch_hook():
    """The currently installed dispatcher hook (None if clear) — lets
    per-engine instrumentation CHAIN an outer hook (chaos injection)
    instead of shadowing it."""
    return _dispatch_hook


def set_degradation(enabled: bool):
    """Toggle the reference fallback; returns the previous setting.
    Off (the default), post-validation failures raise; on, they are
    served by the reference path after one warning."""
    global _degrade_enabled
    prev, _degrade_enabled = _degrade_enabled, bool(enabled)
    return prev


def reset_fallback_warnings() -> None:
    """Forget which entry points already warned (tests)."""
    _FALLBACK_WARNED.clear()


@contextlib.contextmanager
def degradation_scope(enabled: bool):
    """Scoped :func:`set_degradation` with guaranteed restore.

    The serving engine wraps each batch in ``degradation_scope(False)``
    so kernel failures surface as exceptions it converts into its OWN
    per-request ladder (retry, then drop a rung, recorded in request
    telemetry) even inside a caller's ``degradation_scope(True)`` —
    two engines in one process never share degradation state
    (docs/serving.md)."""
    prev = set_degradation(enabled)
    try:
        yield
    finally:
        set_degradation(prev)


@contextlib.contextmanager
def dispatch_hook_scope(hook):
    """Scoped :func:`set_dispatch_hook` with guaranteed restore — the
    save/restore idiom chaos tests and per-engine instrumentation use
    so a raising body cannot leak a hook into unrelated callers."""
    prev = set_dispatch_hook(hook)
    try:
        yield
    finally:
        set_dispatch_hook(prev)


def _consult_dispatch_hook(**context):
    """Run the installed hook; returns its result (a ``finish``
    callable, or None).  A raising hook aborts the kernel path."""
    if _dispatch_hook is not None:
        return _dispatch_hook(context)
    return None


def _finish_dispatch(finish, out=None, error=None) -> None:
    """Close a hook's measurement.  Observability must never break the
    dispatch: a non-callable ``finish`` is ignored and a raising one is
    swallowed (debug-logged) — the kernel result/degradation decision
    was already made."""
    if not callable(finish):
        return
    try:
        finish(out=out, error=error)
    except Exception as e:  # noqa: BLE001 — never propagate from obs
        _log.debug("dispatch finish hook raised: %s: %s",
                   type(e).__name__, e)


def _degraded(key: tuple, err: Exception, fallback):
    """Run ``fallback()`` after logging the first degradation of
    ``key``; re-raise if degradation is disabled."""
    if not _degrade_enabled:
        raise err
    if key not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add(key)
        _log.warning(
            "%s: bounded kernel path failed (%s: %s); degrading to the "
            "XLA reference path (warned once per entry point — see "
            "docs/robustness.md)", "/".join(key), type(err).__name__, err)
    return fallback()


def check_channel_tiles(c: int, m: int, tile_c: int | None,
                        tile_m: int | None = None) -> None:
    """Reject channel tiles that don't divide the layer — a clear
    ``ValueError`` at the public entry instead of a deep Pallas
    BlockSpec shape error (or a bare kernel assert) later."""
    if tile_c is not None and c % tile_c != 0:
        raise ValueError(
            f"tile_c={tile_c} does not divide C={c}; the fused kernels "
            f"step the channel axis in contiguous tile_c chunks — pass a "
            f"divisor of C (or tile_c=None for the Sec. 3.2 chooser, "
            f"which snaps to divisors)")
    if tile_m is not None and m % tile_m != 0:
        raise ValueError(
            f"tile_m={tile_m} does not divide M={m}; the output-channel "
            f"grid axis needs a divisor of M (or tile_m=None for the "
            f"chooser)")


def check_batch_split(n: int, *, cores: int = 1,
                      shard_of: int | None = None) -> None:
    """Reject batch splits that don't divide the batch — a clear
    ``ValueError`` at the public entry (à la ``check_channel_tiles``)
    instead of a deep Pallas grid assert / shard_map shape error later.

    ``shard_of`` names the pre-shard global batch in the message when
    ``n`` is already a per-device shard (mesh sharding composes with
    the core split: each device's shard is further split over cores).
    """
    if cores < 1:
        raise ValueError(f"cores={cores} must be >= 1")
    if n % cores != 0:
        ctx = (f" (per-device shard of global batch N={shard_of})"
               if shard_of is not None else "")
        raise ValueError(
            f"cores={cores} does not divide batch N={n}{ctx}; the "
            f"Megacore backward splits the batch grid axis into "
            f"per-core shards — pass a divisor of the batch (or "
            f"cores=1 for the sequential backward kernel)")


@dataclasses.dataclass(frozen=True)
class _ShardSpec:
    """Hashable mesh context of one batch-sharded deform_conv call."""
    mesh: Mesh
    axes: tuple[str, ...]

    def pspec(self, rank: int) -> P:
        """Full-rank PartitionSpec sharding dim 0 over the batch axes."""
        return P(self.axes, *([None] * (rank - 1)))


def resolve_batch_shard(n: int, *, shard_batch: bool | None = None,
                        cores: int = 1) -> _ShardSpec | None:
    """Decide whether (and how) to shard the batch axis over the active
    mesh, validating the core split either way.

    * ``shard_batch=None`` (auto): shard iff a mesh is active under
      ``distributed.sharding.use_rules`` and its batch-mapped axes
      divide ``n``; otherwise run unsharded (same silent-fallback
      philosophy as ``logical_spec``).
    * ``shard_batch=True``: require sharding — no active mesh or a
      non-dividing batch raises a ``ValueError`` naming the sizes.
    * ``shard_batch=False``: never shard.
    """
    got = batch_mesh_axes() if shard_batch is not False else None
    if got is None:
        if shard_batch:
            raise ValueError(
                "shard_batch=True but no mesh maps the 'batch' logical "
                "axis — activate one with distributed.sharding."
                "use_rules(mesh=...) (axes of size > 1 required)")
        check_batch_split(n, cores=cores)
        return None
    mesh, axes, size = got
    if n % size != 0:
        if shard_batch:
            raise ValueError(
                f"batch N={n} does not divide the mesh batch axes "
                f"{axes} (total size {size}); the shard_map kernel "
                f"path needs equal per-device shards — pad the batch "
                f"to a multiple of {size} or pass shard_batch=False")
        check_batch_split(n, cores=cores)
        return None
    check_batch_split(n // size, cores=cores, shard_of=n)
    return _ShardSpec(mesh=mesh, axes=axes)


@functools.partial(
    jax.jit,
    static_argnames=("kernel_size", "stride", "dilation", "offset_bound",
                     "tile_h", "tile_w", "tile_c", "dataflow", "interpret"))
def deform_sample(x: Array, offsets: Array, *, kernel_size: int = 3,
                  stride: int = 1, dilation: int = 1,
                  offset_bound: float | None = None,
                  tile_h: int | None = 8, tile_w: int | None = None,
                  tile_c: int | None = None,
                  dataflow: str = DEFAULT_DATAFLOW,
                  interpret: bool | None = None) -> Array:
    """Stage 1: bilinear patch sampling.

    x: (N, H, W, C); offsets: (N, Ho, Wo, 2*K*K) raw offset-conv output.
    Returns (N, Ho, Wo, K*K, C).
    """
    n, h, w, c = x.shape
    ho, wo = offsets.shape[1], offsets.shape[2]
    k2 = kernel_size * kernel_size

    if offset_bound is None:
        # Unbounded model: irregular-gather baseline (paper's lambda=0).
        cfg = DCLConfig(in_channels=c, out_channels=1,
                        kernel_size=kernel_size, stride=stride,
                        dilation=dilation)
        return sample_patches(x, offsets.reshape(n, ho, wo, k2, 2), cfg)

    if interpret is None:
        interpret = default_interpret()
    check_channel_tiles(c, c, tile_c)

    if dataflow == "banded":
        th = tile_h or 8
        pad_h = (-ho) % th
        if pad_h:
            offsets = jnp.pad(offsets, ((0, 0), (0, pad_h), (0, 0), (0, 0)))
        bands, n_tiles = _plan.pad_and_band(
            x, kernel_size=kernel_size, stride=stride, dilation=dilation,
            offset_bound=offset_bound, tile_h=th, ho=ho + pad_h)
        patches = deform_sample_banded(
            bands, offsets, kernel_size=kernel_size, stride=stride,
            dilation=dilation, offset_bound=offset_bound, tile_h=th,
            tile_c=tile_c, interpret=interpret)
        return patches[:, :ho]

    if dataflow != "zero_copy":
        raise ValueError(
            f"unknown dataflow {dataflow!r}; expected 'zero_copy' or "
            f"'banded'")
    th, tw, tc, _ = resolve_tiles(
        h, w, c, c, kernel_size=kernel_size, stride=stride,
        dilation=dilation, offset_bound=offset_bound, tile_h=tile_h,
        tile_w=tile_w, tile_c=tile_c, tile_m=c, objective="forward")
    th, tw = min(th, ho), min(tw, wo)
    pad_h, pad_w = (-ho) % th, (-wo) % tw
    if pad_h or pad_w:
        offsets = jnp.pad(offsets,
                          ((0, 0), (0, pad_h), (0, pad_w), (0, 0)))
    xp = _plan.pad_zerocopy(
        x, kernel_size=kernel_size, stride=stride, dilation=dilation,
        offset_bound=offset_bound, tile_h=th, tile_w=tw,
        ho=ho + pad_h, wo=wo + pad_w)
    patches = deform_sample_zerocopy(
        xp, offsets, kernel_size=kernel_size, stride=stride,
        dilation=dilation, offset_bound=offset_bound, tile_h=th, tile_w=tw,
        tile_c=tc, interpret=interpret)
    return patches[:, :ho, :wo]


# ---------------------------------------------------------------------------
# Bounded path: custom VJP over the emitted kernels.
#
# Forward runs the zero-copy (or legacy banded) fused kernel; backward
# runs the fused zero-copy backward kernel of ``deform_conv_bwd.py``
# regardless of the forward dataflow (gradients are a property of the
# math, not the dataflow — both forwards match ``ref.py`` bit-for-near).
# Residuals are just (x, offsets, w): patches are recomputed in-kernel
# from the Eq. 6 band, which the traffic model favors over saving the
# (N, Ho, Wo, K^2, C) patch tensor (see ``deform_conv_bwd.py``).  The
# runner bodies live in ``kernels.plan``.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _deform_conv_bounded(spec: _DCSpec, x: Array, offsets: Array,
                         w: Array) -> Array:
    return _plan.bounded_forward(spec, x, offsets, w)


def _deform_conv_bounded_fwd(spec, x, offsets, w):
    return _plan.bounded_forward(spec, x, offsets, w), (x, offsets, w)


def _deform_conv_bounded_bwd(spec, res, gy):
    x, offsets, w = res
    return _plan.bounded_backward(spec, x, offsets, w, gy)


_deform_conv_bounded.defvjp(_deform_conv_bounded_fwd,
                            _deform_conv_bounded_bwd)


# ---------------------------------------------------------------------------
# Mesh-sharded bounded path: shard_map over the batch axis, custom VJP
# with an explicit d_weights psum epilogue.
#
# The custom_vjp wraps the shard_maps (one for forward, one for
# backward) rather than the other way round, so gradient correctness
# never depends on shard_map's transpose rules: each device runs the
# zero-copy kernels on its batch shard; d_input/d_offsets are
# batch-sharded like their primals, and the replicated weights'
# cotangent is psummed across the batch mesh axes inside the backward
# body (this also covers the QAT fake-quant path — the STE wrappers
# act on the replicated weights *outside* this function, so the psummed
# kernel dw is exactly the cotangent they consume).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _deform_conv_sharded(spec: _DCSpec, shard: _ShardSpec, x: Array,
                         offsets: Array, w: Array) -> Array:
    pb = shard.pspec(4)
    fn = jax.shard_map(functools.partial(_plan.bounded_forward, spec),
                       mesh=shard.mesh,
                       in_specs=(pb, pb, P(None, None, None)),
                       out_specs=pb, check_vma=False)
    return fn(x, offsets, w)


def _deform_conv_sharded_fwd(spec, shard, x, offsets, w):
    return _deform_conv_sharded(spec, shard, x, offsets, w), (x, offsets, w)


def _deform_conv_sharded_bwd(spec, shard, res, gy):
    x, offsets, w = res
    pb = shard.pspec(4)
    rep_w = P(None, None, None)

    def body(x, offsets, w, gy):
        dx, doff, dw = _plan.bounded_backward(spec, x, offsets, w, gy)
        # psum epilogue: w is replicated across the batch axes, so its
        # cotangent is the sum of every shard's partial d_weights.
        return dx, doff, jax.lax.psum(dw, shard.axes)

    fn = jax.shard_map(body, mesh=shard.mesh,
                       in_specs=(pb, pb, rep_w, pb),
                       out_specs=(pb, pb, rep_w), check_vma=False)
    return fn(x, offsets, w, gy)


_deform_conv_sharded.defvjp(_deform_conv_sharded_fwd,
                            _deform_conv_sharded_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("kernel_size", "stride", "dilation", "offset_bound",
                     "tile_h", "tile_w", "tile_c", "tile_m", "dataflow",
                     "precision", "cores", "shard", "spatial", "interpret",
                     "dw_flush_every_step"))
def _deform_conv_impl(x: Array, offsets: Array, w: Array, *,
                      kernel_size: int, stride: int, dilation: int,
                      offset_bound: float | None,
                      tile_h: int | None, tile_w: int | None,
                      tile_c: int | None, tile_m: int | None,
                      dataflow: str, precision: str, cores: int,
                      shard: _ShardSpec | None,
                      spatial: _spatial.SpatialSpec | None,
                      x_scale: Array | None, w_scale: Array | None,
                      interpret: bool | None,
                      dw_flush_every_step: bool | None = None) -> Array:
    # NOTE: argument validation lives in the un-jitted ``deform_conv``
    # wrapper (hoisted in PR 6 so validation errors always raise while
    # post-validation failures can degrade to the reference path).
    n, h, w_, c = x.shape
    ho, wo = offsets.shape[1], offsets.shape[2]
    k2 = kernel_size * kernel_size
    m = w.shape[-1]

    if precision == "int8":
        if interpret is None:
            interpret = default_interpret()
        if spatial is not None:
            return _spatial.spatial_int8_forward(
                x, offsets, w, kernel_size=kernel_size, stride=stride,
                dilation=dilation, offset_bound=offset_bound,
                tile_h=tile_h, tile_w=tile_w, tile_c=tile_c,
                tile_m=tile_m, x_scale=x_scale, w_scale=w_scale,
                interpret=interpret, sspec=spatial)
        return int8_forward(
            x, offsets, w, kernel_size=kernel_size, stride=stride,
            dilation=dilation, offset_bound=offset_bound, tile_h=tile_h,
            tile_w=tile_w, tile_c=tile_c, tile_m=tile_m,
            x_scale=x_scale, w_scale=w_scale, interpret=interpret)

    if offset_bound is None:
        cfg = DCLConfig(in_channels=c, out_channels=m,
                        kernel_size=kernel_size, stride=stride,
                        dilation=dilation)
        patches = sample_patches(x, offsets.reshape(n, ho, wo, k2, 2), cfg)
        y = jnp.einsum("nhwkc,kcm->nhwm", patches, w,
                       preferred_element_type=jnp.float32)
        return y.astype(x.dtype)

    if interpret is None:
        interpret = default_interpret()
    spec = _DCSpec(kernel_size=kernel_size, stride=stride, dilation=dilation,
                   offset_bound=offset_bound, tile_h=tile_h, tile_w=tile_w,
                   tile_c=tile_c, tile_m=tile_m, dataflow=dataflow,
                   interpret=interpret, cores=cores,
                   dw_flush_every_step=dw_flush_every_step)
    if spatial is not None:
        return _spatial.deform_conv_spatial(spec, spatial, x, offsets, w)
    if shard is not None:
        return _deform_conv_sharded(spec, shard, x, offsets, w)
    return _deform_conv_bounded(spec, x, offsets, w)


@functools.partial(
    jax.jit,
    static_argnames=("kernel_size", "stride", "dilation", "offset_bound",
                     "precision"))
def _reference_impl(x: Array, offsets: Array, w: Array, *,
                    kernel_size: int, stride: int, dilation: int,
                    offset_bound: float, precision: str,
                    x_scale: Array | None,
                    w_scale: Array | None) -> Array:
    """The platform='xla_ref' lowering (``launch.platform``): the
    degradation ladder's reference forms of the bounded arithmetic,
    compiled as ordinary XLA — the parity baseline the tuner and the
    test-suite compare the emitted kernels against.  Differentiable
    (plain XLA graph), so the training objective works here too."""
    if precision == "int8":
        from repro.quant.qat import fake_quant_dcl_reference
        return fake_quant_dcl_reference(
            x, offsets, w, kernel_size=kernel_size, stride=stride,
            dilation=dilation, offset_bound=offset_bound,
            x_scale=x_scale, w_scale=w_scale)
    return _plan.reference_forward(
        x, offsets, w, kernel_size=kernel_size, stride=stride,
        dilation=dilation, offset_bound=offset_bound)


def deform_conv(x: Array, offsets: Array, w: Array, *, kernel_size: int = 3,
                stride: int = 1, dilation: int = 1,
                offset_bound: float | None = None,
                tile_h: int | None = None, tile_w: int | None = None,
                tile_c: int | None = None, tile_m: int | None = None,
                dataflow: str = DEFAULT_DATAFLOW,
                precision: str = "fp32",
                cores: int = 1,
                shard_batch: bool | None = None,
                shard_spatial: bool | None = None,
                x_scale: Array | None = None,
                w_scale: Array | None = None,
                interpret: bool | None = None,
                dw_flush_every_step: bool | None = None) -> Array:
    """Fused DCL stage 1+2: y = g(x, o) * w_deform  (Eq. 2).

    x: (N, H, W, C); offsets: (N, Ho, Wo, 2*K*K); w: (K*K, C, M).
    Returns (N, Ho, Wo, M).  Unspecified tile sizes are resolved by the
    Sec. 3.2 chooser against the combined fwd+bwd zero-copy traffic
    model.  The bounded path is differentiable end-to-end: ``jax.grad``
    routes through the fused backward kernel of ``deform_conv_bwd.py``
    (a ``jax.custom_vjp``), never through an XLA gather/scatter.

    ``cores`` splits the backward kernel's batch grid axis per Megacore
    core (``parallel`` dimension semantics + per-core d_weights reduce;
    must divide the per-device batch — ``check_batch_split`` raises the
    friendly error).  ``shard_batch`` controls the data-parallel
    ``shard_map`` wrap of the bounded fp32 path over the active mesh's
    batch axes (see ``resolve_batch_shard``: None = auto, True =
    require, False = never).  Sharding is resolved OUTSIDE this
    function's own jit boundary from
    ``distributed.sharding.current_rules()`` — the mesh context is a
    static cache key of ``_deform_conv_impl``, so eager/top-level calls
    under different ``use_rules`` contexts never reuse a stale layout.
    The usual jit caveat still applies one level up: a CALLER's
    ``jax.jit`` bakes the context seen at its own trace time into its
    cache (the Trainer builds its step inside ``use_rules(mesh=...)``
    and keeps one mesh per instance for exactly this reason); pass
    ``shard_batch=True`` to fail loudly instead of silently running
    unsharded when the mesh matters.

    ``precision="int8"`` (bounded zero-copy only) runs the quantized
    inference datapath: int8 band DMA + int8 MXU contraction with int32
    accumulation, fp32 bilinear coefficients, fused per-out-channel
    dequant epilogue.  ``x_scale`` (per-tensor) / ``w_scale``
    (per-out-channel, shape (M,)) override the dynamic absmax observers
    with calibrated values (``repro.quant.calibrate``); tiles resolve
    against the int8 dtype-aware budgets (4x Eq. 6 band density per
    VMEM byte).

    ``shard_spatial=True`` (ISSUE 10) height-shards the bounded
    zero-copy call over the mesh axis the 'spatial' logical axis maps
    to (``distributed.spatial``): one ``lax.ppermute`` halo-exchange
    pair of the statically bounded ``B + ceil(K/2)`` rows per call,
    then the unmodified per-shard kernel — single-image latency
    scaling for megapixel inputs.  Strictly opt-in (None/False = off);
    requires an active mesh, ``H % (stride*shards) == 0``, and the
    zero-copy dataflow.  Works for fp32 (differentiable — halo
    gradients are returned to their owning shards and ``d_weights`` is
    psummed) and int8 (inference, scales hoisted above the shard_map);
    composes with ``shard_batch`` into a spatial x data 2-D mesh and
    with the Megacore ``cores`` split.
    """
    # -- validation (always raises; never degraded) -------------------
    c, m = x.shape[-1], w.shape[-1]
    if precision not in ("fp32", "int8"):
        raise ValueError(
            f"unknown precision {precision!r}; expected 'fp32' or 'int8'")
    if dataflow not in ("zero_copy", "banded"):
        raise ValueError(
            f"unknown dataflow {dataflow!r}; expected 'zero_copy' or "
            f"'banded'")
    check_channel_tiles(c, m, tile_c, tile_m)
    if precision == "int8":
        if offset_bound is None:
            raise ValueError(
                "precision='int8' requires a trained offset_bound — the "
                "quantized datapath exists because Eq. 6 bounds the band; "
                "the unbounded gather baseline has no int8 kernel")
        if dataflow != "zero_copy":
            raise ValueError(
                f"precision='int8' supports only the zero-copy dataflow "
                f"(got {dataflow!r})")

    shard = None
    spatial = None
    if shard_spatial:
        if offset_bound is None:
            raise ValueError(
                "shard_spatial=True requires a trained offset_bound — "
                "the halo exchange is statically bounded by Eq. 5/6 "
                "(B + ceil(K/2) rows); the unbounded gather baseline "
                "has no bounded halo and partitions via GSPMD instead")
        if dataflow != "zero_copy":
            raise ValueError(
                f"shard_spatial=True supports only the zero-copy "
                f"dataflow (got {dataflow!r}); the legacy banded path "
                f"materializes full-width bands and has no per-shard "
                f"slab to run on")
    if offset_bound is not None and precision == "fp32":
        shard = resolve_batch_shard(x.shape[0], shard_batch=shard_batch,
                                    cores=cores)
    else:
        if shard_batch:
            raise ValueError(
                "shard_batch=True requires the bounded fp32 kernel path "
                "(offset_bound set, precision='fp32'); the unbounded "
                "gather baseline and the int8 inference datapath "
                "partition via GSPMD instead")
        if cores != 1:
            raise ValueError(
                f"cores={cores} applies to the bounded fp32 kernel path "
                f"(offset_bound set, precision='fp32') — only its fused "
                f"backward has the Megacore batch split; this call "
                f"dispatches the "
                f"{'int8 inference' if precision == 'int8' else 'unbounded gather'} "
                f"path, so pass cores=1")
        if dw_flush_every_step is not None:
            raise ValueError(
                f"dw_flush_every_step={dw_flush_every_step} applies to "
                f"the bounded fp32 kernel path (offset_bound set, "
                f"precision='fp32') — it is the d_weights flush cadence "
                f"of the fused backward kernel; pass None here")
    if shard_spatial:
        # Spatial sharding resolves AFTER the batch shard so a 2-D
        # spatial x data mesh folds the batch axes into one shard_map
        # (the SpatialSpec carries them; the plain batch path is then
        # subsumed).  Validation (active mesh, even height split,
        # halo-thin shards) raises inside resolve_spatial_shard.
        spatial = _spatial.resolve_spatial_shard(
            x.shape[1], shard_spatial=True, stride=stride,
            kernel_size=kernel_size, dilation=dilation,
            offset_bound=offset_bound,
            batch_axes=shard.axes if shard is not None else ())
        shard = None

    from repro.launch.platform import current_platform
    plat = current_platform()

    def _impl():
        if plat == "xla_ref":
            # platform='xla_ref' (launch.platform): the reference rung
            # promoted to a first-class lowering — the same arithmetic
            # as the bounded kernels, emitted as a plain XLA graph (no
            # Pallas at all).  Still dispatched through the hook seam
            # so the obs recorder / tuner time it like any backend.
            return _reference_impl(
                x, offsets, w, kernel_size=kernel_size, stride=stride,
                dilation=dilation, offset_bound=offset_bound,
                precision=precision, x_scale=x_scale, w_scale=w_scale)
        return _deform_conv_impl(
            x, offsets, w, kernel_size=kernel_size, stride=stride,
            dilation=dilation, offset_bound=offset_bound, tile_h=tile_h,
            tile_w=tile_w, tile_c=tile_c, tile_m=tile_m, dataflow=dataflow,
            precision=precision, cores=cores, shard=shard, spatial=spatial,
            x_scale=x_scale, w_scale=w_scale, interpret=interpret,
            dw_flush_every_step=dw_flush_every_step)

    if offset_bound is None:
        # Unbounded gather baseline IS the XLA reference path — there is
        # no lower rung to degrade to.
        return _impl()

    finish = None
    try:
        finish = _consult_dispatch_hook(
            op="deform_conv", precision=precision, dataflow=dataflow,
            shape=tuple(x.shape), offset_bound=offset_bound,
            kernel_size=kernel_size, stride=stride, dilation=dilation,
            m=m, cores=cores, platform=plat,
            spatial_shards=spatial.shards if spatial is not None else 1)
        out = _impl()
        _finish_dispatch(finish, out=out)
        return out
    except Exception as e:  # noqa: BLE001 — bounded-path failure
        _finish_dispatch(finish, error=e)
        def _fallback():
            if precision == "int8":
                from repro.quant.qat import fake_quant_dcl_reference
                return fake_quant_dcl_reference(
                    x, offsets, w, kernel_size=kernel_size, stride=stride,
                    dilation=dilation, offset_bound=offset_bound,
                    x_scale=x_scale, w_scale=w_scale)
            return _plan.reference_forward(
                x, offsets, w, kernel_size=kernel_size, stride=stride,
                dilation=dilation, offset_bound=offset_bound)
        return _degraded(("deform_conv", precision), e, _fallback)


@functools.partial(
    jax.jit,
    static_argnames=("kernel_size", "stride", "dilation", "offset_bound",
                     "tile_h", "tile_w", "tile_c", "tile_m", "emit",
                     "interpret"))
def _deform_conv_chain_impl(x: Array, w: Array, w_offset: Array,
                            b_offset: Array, b_deform: Array | None, *,
                            kernel_size: int, stride: int, dilation: int,
                            offset_bound: float, x_scale, w_scale,
                            w_offset_scale, y_scale,
                            tile_h: int | None, tile_w: int | None,
                            tile_c: int | None, tile_m: int | None,
                            emit: str, interpret: bool | None) -> Array:
    if interpret is None:
        interpret = default_interpret()
    return chain_forward(
        x, w, w_offset, b_offset, b_deform, kernel_size=kernel_size,
        stride=stride, dilation=dilation, offset_bound=offset_bound,
        x_scale=x_scale, w_scale=w_scale, w_offset_scale=w_offset_scale,
        y_scale=y_scale, tile_h=tile_h, tile_w=tile_w, tile_c=tile_c,
        tile_m=tile_m, emit=emit, interpret=interpret)


def deform_conv_chain(x: Array, w: Array, w_offset: Array,
                      b_offset: Array, b_deform: Array | None = None, *,
                      kernel_size: int = 3, stride: int = 1,
                      dilation: int = 1, offset_bound: float,
                      x_scale, w_scale=None, w_offset_scale=None,
                      y_scale=None,
                      tile_h: int | None = None, tile_w: int | None = None,
                      tile_c: int | None = None, tile_m: int | None = None,
                      emit: str = "int8",
                      interpret: bool | None = None) -> Array:
    """One chained int8 DCL layer: fused offset conv + int8 emission.

    x: (N, H, W, C) — int8 values on the ``x_scale`` grid (the previous
    chained layer's emission) or fp32 (the chain head, quantized here
    with ``x_scale``).  w: (K*K, C, M) fp32 deform weights; w_offset:
    (K*K, C, 2*K*K) fp32 offset-conv weights; b_offset/b_deform the
    biases (the deform bias is folded into the requant epilogue —
    int8 emission must quantize ``y + b``, not ``y``).

    Returns (N, Ho, Wo, M) int8 on the ``y_scale`` grid (``emit="int8"``
    — ``y_scale`` is the NEXT layer's activation scale, required) or
    fp32 (``emit="fp32"``, the chain tail).  Offsets never exist in
    HBM: the offset conv runs in-kernel over the staged Eq. 6 band
    (requires ``tile_c == C`` — a clear ``ValueError`` otherwise).
    Training chained models uses the STE reference
    (``repro.quant.qat.fake_quant_dcl_chain_reference``) — this entry
    is the inference datapath.
    """
    # -- validation (always raises; never degraded) -------------------
    if offset_bound is None:
        raise ValueError(
            "deform_conv_chain requires a trained offset_bound — the "
            "fused offset stage exists because Eq. 6 bounds the band")
    if x_scale is None:
        raise ValueError(
            "deform_conv_chain requires x_scale: chained layers exchange "
            "int8 values whose grid must be pinned by calibration "
            "(repro.quant.calibrate — the table's per-layer x_scale)")
    if emit not in ("int8", "fp32"):
        raise ValueError(
            f"unknown emit {emit!r}; expected 'int8' (chained) or 'fp32' "
            f"(chain tail)")
    if emit == "int8" and y_scale is None:
        raise ValueError(
            "emit='int8' requires y_scale (the NEXT layer's activation "
            "scale — the per-channel requant target grid); pass "
            "emit='fp32' for the chain tail instead")
    c = x.shape[-1]
    if tile_c is not None and tile_c != c:
        raise ValueError(
            f"tile_c={tile_c} is incompatible with chaining: the fused "
            f"offset-conv stage needs the whole channel extent staged "
            f"per band (tile_c == C = {c}), since the offsets must be "
            f"complete before the first bilinear sample consumes them — "
            f"pass tile_c=None (or C) for chained layers")

    def _chain_reference():
        # The reference form of the chained layer: the STE chain oracle
        # (same quantization boundaries on the XLA graph), re-quantized
        # onto the emission grid so chained consumers see the same int8
        # plane the kernel would have produced.  Serves BOTH the
        # degradation fallback and the platform='xla_ref' lowering.
        from repro.quant.qat import fake_quant_dcl_chain_reference
        from repro.quant.qtypes import quantize_values

        sx = jnp.asarray(x_scale, jnp.float32)
        xf = (x.astype(jnp.float32) * sx if x.dtype == jnp.int8
              else x)
        y, _ = fake_quant_dcl_chain_reference(
            xf, w, w_offset, b_offset, b_deform,
            kernel_size=kernel_size, stride=stride, dilation=dilation,
            offset_bound=offset_bound, x_scale=x_scale,
            w_scale=w_scale, w_offset_scale=w_offset_scale,
            y_scale=y_scale if emit == "int8" else None)
        if emit == "int8":
            return quantize_values(y, jnp.asarray(y_scale, jnp.float32))
        return y

    from repro.launch.platform import current_platform
    plat = current_platform()

    finish = None
    try:
        finish = _consult_dispatch_hook(
            op="deform_conv_chain", emit=emit, shape=tuple(x.shape),
            offset_bound=offset_bound, kernel_size=kernel_size,
            stride=stride, dilation=dilation, m=w.shape[-1], cores=1,
            platform=plat)
        if plat == "xla_ref":
            out = _chain_reference()
        else:
            out = _deform_conv_chain_impl(
                x, w, w_offset, b_offset, b_deform,
                kernel_size=kernel_size, stride=stride, dilation=dilation,
                offset_bound=offset_bound, x_scale=x_scale,
                w_scale=w_scale, w_offset_scale=w_offset_scale,
                y_scale=y_scale, tile_h=tile_h, tile_w=tile_w,
                tile_c=tile_c, tile_m=tile_m, emit=emit,
                interpret=interpret)
        _finish_dispatch(finish, out=out)
        return out
    except Exception as e:  # noqa: BLE001 — bounded-path failure
        _finish_dispatch(finish, error=e)
        return _degraded(("deform_conv_chain", emit), e, _chain_reference)
