"""Slot-based detection serving engine for the bounded DCL models.

The LM engine (``serve.engine``) streams tokens through a static decode
batch; detection requests are single-shot, so the slot discipline here
is admit -> one batched forward -> retire, with the static-shape story
carried by *shape buckets*: a small fixed set of square resolutions,
each warmed at engine start with a memoized tile plan
(``kernels.plan.warm_tile_cache`` over the per-layer ``resolve_tiles``
lru-cache).  Every step serves one bucket — up to ``slots`` queued
requests padded into one static batch — so the jit caches stay closed
over ``len(buckets)`` shapes per datapath rung.

The default datapath is the paper's production configuration:
``quant="int8_chain"`` (fused in-kernel offset conv, int8 -> int8 layer
handoff) with calibration scale tables loaded at engine start.

Robustness layer (docs/serving.md):

* per-request deadlines — checked at admission, swept between steps,
  and re-checked after the serving step (a ``slow_step`` stall lands
  here); expiry is the typed ``deadline_exceeded`` outcome.
* bounded admission queue — ``serve.admission``; overload is shed
  (``shed_oldest``) or bounced (``reject_new``), never an exception.
* transient step failures — the failed batch (and ONLY that batch: the
  affected slots) is replayed with exponential backoff, up to
  ``max_retries`` per rung.
* per-request degradation ladder — persistent failures drop the batch
  one rung (int8_chain -> int8 -> fp32 kernel -> XLA reference) and
  replay.  The engine runs each batch under
  ``ops.degradation_scope(False)`` so kernel failures surface HERE and
  are recorded in each affected request's telemetry (``ladder``,
  ``degraded``) — not in ``ops``'s process-global warn-once fallback,
  so two engines in one process keep independent ladders and every
  degraded request reports its own rung.

The model forward is one device program per (bucket, rung): the
engine jits ``R.forward`` on the first batch a bucket serves on a rung
and launches that program on every later one, so a step enqueues one
program instead of one per primitive.  The programs belong to the
engine, and each is traced under ``ops.degradation_scope(False)``: a
kernel failure, or a raising ``ops.set_dispatch_hook`` (consulted once,
when the engine first traces the rung), fails the call and takes this
engine's ladder.  ``serve_forward_traces_total{bucket, rung}`` counts
the completed traces; in a steady run it stays at 1 per (bucket, rung)
served.

Program spans (``repro.obs.trace``): ``serve/step`` and under it
``serve/batch`` (host pad and device put), ``serve/forward`` (one
program launch per ladder attempt, with its ``rung``), ``serve/fetch``
(waits for the device and copies ``cls``/``box`` to the host) and
``serve/retire``.  The engine never blocks on the device inside the
forward: the host waits once, in ``serve/fetch``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import time
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.distributed.sharding import use_rules
from repro.kernels import ops, plan
from repro.models import resnet_dcn as R
from repro.obs import MetricsRegistry, Tracer
from repro.obs import trace as _trace

from .admission import (AdmissionConfig, AdmissionQueue, DetRequest,
                        MalformedRequest, resolve_bucket)

__all__ = ["LADDER", "DCLServeConfig", "DCLServingEngine",
           "bucket_layer_dims"]

# Degradation ladder, top (production) rung first.  Mirrors the ops.py
# fallback ladder; the bottom rung never touches the kernel path.
LADDER = ("int8_chain", "int8", "fp32_kernel", "fp32_ref")


@dataclasses.dataclass(frozen=True)
class DCLServeConfig:
    buckets: tuple[int, ...] = (64, 128)
    slots: int = 4                   # static batch rows per step
    quant: str = "int8_chain"        # entry rung of LADDER
    strict_buckets: bool = True      # False: pad up to the next bucket
    queue_capacity: int = 64
    shed_policy: str = "reject_new"  # reject_new | shed_oldest
    max_retries: int = 2             # same-rung replays before degrading
    retry_backoff: float = 0.0       # seconds; doubles per consecutive retry
    default_deadline: float | None = None   # seconds from submit; None = off
    # Deadline-aware scheduling (ISSUE 10): a partial batch is held up
    # to batch_window seconds for more same-bucket arrivals; 0.0 serves
    # partials immediately (the pre-ISSUE-10 behavior).
    batch_window: float = 0.0
    # Spatial sharding (ISSUE 10): ((bucket, shards), ...) — the listed
    # buckets run their kernel rungs height-sharded over `shards`
    # devices with the bounded halo exchange (distributed.spatial).
    # Spatial buckets ladder from "int8": the chained datapath's fused
    # offset stage cannot be halo-split.
    spatial_shards: tuple[tuple[int, int], ...] = ()

    def __post_init__(self):
        if self.quant not in LADDER:
            raise ValueError(
                f"unknown serve datapath {self.quant!r}; expected one "
                f"of {LADDER} (the degradation ladder runs from the "
                f"chosen rung down)")
        if not self.buckets:
            raise ValueError("at least one shape bucket is required — "
                             "static compilation needs a closed shape set")
        if self.slots < 1:
            raise ValueError(f"slots must be >= 1 (got {self.slots})")
        if self.batch_window < 0:
            raise ValueError(
                f"batch_window must be >= 0 (got {self.batch_window})")
        for entry in self.spatial_shards:
            if len(entry) != 2:
                raise ValueError(
                    f"spatial_shards entries are (bucket, shards) pairs "
                    f"(got {entry!r})")
            b, s = entry
            if b not in self.buckets:
                raise ValueError(
                    f"spatial_shards names bucket {b} which is not in "
                    f"buckets {self.buckets}")
            if s < 1:
                raise ValueError(
                    f"spatial_shards for bucket {b} must be >= 1 "
                    f"(got {s})")

    def spatial_shards_for(self, bucket: int | None) -> int:
        for b, s in self.spatial_shards:
            if b == bucket:
                return s
        return 1


def bucket_layer_dims(cfg: R.ResNetDCNConfig, res: int) -> dict[str, dict]:
    """Dims of every DCL invocation at input resolution ``res`` — the
    shapes the bucket's tile plans are resolved against."""
    dims: dict[str, dict] = {}
    e = res // 4                       # stride-2 stem + stride-2 maxpool
    bi = 0
    for s, (n_blocks, width) in enumerate(zip(cfg.stage_sizes, cfg.widths)):
        for b in range(n_blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            if cfg.is_dcn(bi):
                mid = width // 4
                dims[f"s{s}b{b}"] = dict(h=e, w=e, c=mid, m=mid,
                                         stride=stride)
            e //= stride
            bi += 1
    return dims


class DCLServingEngine:
    """See module docstring.  ``clock``/``sleep`` are injectable for
    deterministic deadline and backoff tests; ``step_hook(step, ctx)``
    and ``admit_hook(request)`` are the chaos seams
    (``resilience.ChaosHooks.serve_step_hook`` / ``admit_hook``).
    ``tap(name, x)`` sees every DCL block's input (``name``) and output
    (``name + "/out"``) of each served batch, in ``R.forward``'s order:
    the program returns them beside ``cls``/``box`` and the engine hands
    them on as device arrays after the launch — how a caller checks the
    served layers against a reference."""

    def __init__(self, params, model_cfg: R.ResNetDCNConfig,
                 serve_cfg: DCLServeConfig, *,
                 scale_table: Mapping[str, Any] | str | None = None,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep,
                 step_hook: Callable[[int, dict], None] | None = None,
                 admit_hook: Callable[[DetRequest], DetRequest] | None = None,
                 tap: Callable[[str, Any], None] | None = None,
                 registry: MetricsRegistry | None = None,
                 tracer: Tracer | None = None):
        self.params = params
        self.scfg = serve_cfg
        self.clock = clock
        self._sleep = sleep
        self.step_hook = step_hook
        self.admit_hook = admit_hook
        self.tap = tap

        # Observability (ISSUE 8).  Each engine defaults to its OWN
        # registry — two engines in one process never share counters,
        # matching the per-engine degradation-ladder isolation.  The
        # tracer defaults to the process-global one resolved at use
        # time (disabled unless a test/launcher opts in).
        self.metrics = registry if registry is not None else MetricsRegistry()
        self._tracer = tracer
        m = self.metrics
        self._c_requests = m.counter(
            "serve_requests_total", "retired requests by outcome and bucket")
        self._c_retries = m.counter(
            "serve_retries_total", "same-rung batch replays")
        self._c_degraded = m.counter(
            "serve_degraded_batches_total", "batches dropped one ladder rung")
        self._c_ladder = m.counter(
            "serve_ladder_total", "requests served per datapath rung")
        self._c_steps = m.counter(
            "serve_steps_total", "engine serving steps")
        self._g_queue = m.gauge(
            "serve_queue_depth", "queued requests after the last step")
        self._h_queue_wait = m.histogram(
            "serve_queue_wait_seconds",
            "submit-to-batch-start wait per bucket")
        self._h_latency = m.histogram(
            "serve_latency_seconds",
            "submit-to-retire latency per bucket and outcome")
        self._c_traces = m.counter(
            "serve_forward_traces_total",
            "completed traces of the jitted forward per bucket and rung")

        if isinstance(scale_table, str):
            from repro.quant.calibrate import load_scale_table
            scale_table = load_scale_table(scale_table)
        self.scale_table = scale_table
        # The programs take the DCLs' scales as an argument: closed
        # over, they would be constants, and every calibration table
        # would compile the program anew.
        self._scales = None if scale_table is None else {
            name: {k: jnp.asarray(v, jnp.float32)
                   for k, v in scale_table[name].items()}
            for name in bucket_layer_dims(model_cfg, max(serve_cfg.buckets))
            if name in scale_table}
        if serve_cfg.quant in ("int8_chain", "int8"):
            if model_cfg.offset_bound is None:
                raise ValueError(
                    f"serve datapath {serve_cfg.quant!r} needs a trained "
                    f"offset_bound on the model config — the bounded "
                    f"band DMA is the whole int8 story (Eq. 6)")
            if scale_table is None:
                raise ValueError(
                    f"serve datapath {serve_cfg.quant!r} needs a "
                    f"calibration scale table at engine start "
                    f"(repro.quant.calibrate_resnet_dcn + "
                    f"save_scale_table); chained layers exchange int8 "
                    f"on pinned activation grids")

        # One model config per ladder rung; the rung is chosen per batch
        # attempt, so all four stay ready.
        self._cfgs = {
            "int8_chain": dataclasses.replace(
                model_cfg, quant="int8_chain", use_kernel=True),
            "int8": dataclasses.replace(
                model_cfg, quant="int8", use_kernel=True),
            "fp32_kernel": dataclasses.replace(
                model_cfg, quant="none", use_kernel=True),
            "fp32_ref": dataclasses.replace(
                model_cfg, quant="none", use_kernel=False),
        }

        # Spatial sharding (ISSUE 10): per-bucket meshes for the
        # height-sharded kernel rungs.  Shard counts are validated
        # against the real device count HERE — a misconfigured engine
        # fails at construction, not on the first sharded request.
        self._spatial_meshes: dict[int, Mesh] = {}
        for b, s in serve_cfg.spatial_shards:
            if s > jax.device_count():
                raise ValueError(
                    f"spatial_shards={s} for bucket {b} exceeds the "
                    f"{jax.device_count()} available device(s) — the "
                    f"height split needs one device per shard")
            if s > 1:
                if model_cfg.offset_bound is None:
                    raise ValueError(
                        f"spatial_shards={s} for bucket {b} needs a "
                        f"trained offset_bound on the model config — the "
                        f"bounded halo exchange is derived from it")
                self._spatial_meshes[b] = Mesh(
                    np.asarray(jax.devices()[:s]), ("model",))

        # Per-bucket plan cache: resolve every DCL tile config now, so
        # the chooser sweep happens at engine start, not first request.
        int8ish = serve_cfg.quant in ("int8_chain", "int8")
        plan_dtype = "int8" if int8ish else None
        self.plans: dict[int, dict[str, tuple]] = {}
        # Per-layer plan provenance (ISSUE 9): "tuned" when the layer's
        # tiles came from the installed autotuner cache (repro.tune),
        # "analytic" for the Sec. 3.2 chooser — surfaced in telemetry()
        # and serve_bench so a cold/ignored cache is visible.  Spatial
        # buckets warm the per-shard (local-height) plans the sharded
        # path actually resolves and tag the provenance with the shard
        # count ("analytic@2shard") — ISSUE 10 satellite: warming the
        # global-height plans would leave every sharded dispatch cold.
        self.plan_sources: dict[int, dict[str, str]] = {}
        if model_cfg.offset_bound is not None:
            for b in serve_cfg.buckets:
                dims = bucket_layer_dims(model_cfg, b)
                shards = serve_cfg.spatial_shards_for(b)
                self.plans[b] = plan.warm_tile_cache(
                    dims,
                    offset_bound=model_cfg.offset_bound,
                    objective="forward",
                    dtype=plan_dtype,
                    spatial_shards=shards)
                suffix = f"@{shards}shard" if shards > 1 else ""
                self.plan_sources[b] = {
                    name: plan.tile_source(
                        d["h"], d["w"], d["c"], d["m"],
                        stride=d.get("stride", 1),
                        offset_bound=model_cfg.offset_bound,
                        objective="forward", dtype=plan_dtype,
                        spatial_shards=shards) + suffix
                    for name, d in dims.items()}

        # (bucket, rung, model forward) -> (jitted forward, names of its
        # taps); built on first use, per engine.
        self._programs: dict[tuple, tuple[Callable, list]] = {}
        self.queue = AdmissionQueue(AdmissionConfig(
            capacity=serve_cfg.queue_capacity,
            policy=serve_cfg.shed_policy))
        self.completed: list[DetRequest] = []
        self.steps = 0
        self._uid = itertools.count()

    @property
    def _tr(self) -> Tracer:
        return self._tracer if self._tracer is not None \
            else _trace.get_tracer()

    @property
    def counters(self) -> dict[str, int]:
        """Legacy counters view, now rendered FROM the metrics registry
        (ISSUE 8): ``{outcome: count}`` summed over buckets, plus
        ``retries`` / ``degraded_batches`` when nonzero — the exact
        shape the pre-obs ad-hoc dict had, so dict-equality callers
        keep working."""
        out: dict[str, int] = {}
        for key, v in self._c_requests.items():
            outcome = dict(key)["outcome"]
            out[outcome] = out.get(outcome, 0) + int(v)
        retries = int(self._c_retries.value())
        if retries:
            out["retries"] = retries
        degraded = int(self._c_degraded.value())
        if degraded:
            out["degraded_batches"] = degraded
        return out

    # -- admission -----------------------------------------------------
    def submit(self, image, *, deadline: float | None = None,
               uid: int | None = None) -> DetRequest:
        """Admit a detection request.  ``deadline`` is seconds from now
        on the engine clock.  The returned request is either queued or
        already retired with a typed outcome (rejected / shed /
        malformed / unbucketable / deadline_exceeded) — admission never
        raises on bad traffic."""
        now = self.clock()
        if deadline is None and self.scfg.default_deadline is not None:
            deadline = self.scfg.default_deadline
        req = DetRequest(
            uid=next(self._uid) if uid is None else uid, image=image,
            deadline=None if deadline is None else now + deadline,
            submitted_at=now)
        self._tr.event("serve/admit", uid=req.uid)
        if self.admit_hook is not None:
            req = self.admit_hook(req) or req

        try:
            arr = np.asarray(req.image)
            if arr.ndim != 3 or arr.shape[-1] != 3 \
                    or not np.issubdtype(arr.dtype, np.number):
                raise MalformedRequest(
                    f"detection request needs a numeric (H, W, 3) "
                    f"image; got shape {arr.shape} dtype {arr.dtype}")
        except Exception as e:
            return self._retire(req, "malformed",
                                f"{type(e).__name__}: {e}")
        try:
            req.bucket = resolve_bucket(arr.shape[0], arr.shape[1],
                                        self.scfg.buckets,
                                        strict=self.scfg.strict_buckets)
        except ValueError as e:
            return self._retire(req, "unbucketable", str(e))
        if req.deadline is not None and now > req.deadline:
            return self._retire(req, "deadline_exceeded",
                                "expired at admission")
        displaced = self.queue.offer(req)
        if displaced is not None:
            self._retire(displaced)
        return req

    def _retire(self, req: DetRequest, outcome: str | None = None,
                error: str = "") -> DetRequest:
        if outcome is not None:
            req.outcome = outcome
            if error:
                req.error = error
        req.done = True
        req.completed_at = self.clock()
        self.completed.append(req)
        bucket = str(req.bucket)
        self._c_requests.inc(outcome=req.outcome, bucket=bucket)
        lat = req.latency_s()
        if lat is not None:
            self._h_latency.observe(lat, bucket=bucket, outcome=req.outcome)
        self._tr.event("serve/retire", uid=req.uid, outcome=req.outcome)
        return req

    # -- serving -------------------------------------------------------
    def step(self) -> int:
        """Expire, pick the most urgent bucket (oldest-deadline-first,
        full batches preferred — ``AdmissionQueue.pick_bucket``), serve
        it.  Returns the number of requests retired this step."""
        before = len(self.completed)
        for req in self.queue.expire(self.clock()):
            self._retire(req)
        bucket = self.queue.pick_bucket(
            slots=self.scfg.slots, now=self.clock(),
            batch_window=self.scfg.batch_window)
        if bucket is None:
            self._g_queue.set(len(self.queue))
            return len(self.completed) - before
        batch = self.queue.take(bucket, self.scfg.slots)
        with self._tr.span("serve/step", step=self.steps, bucket=bucket,
                           size=len(batch)):
            now = self.clock()
            for r in batch:
                self._h_queue_wait.observe(now - r.submitted_at,
                                           bucket=str(bucket))
            if self.step_hook is not None:
                self.step_hook(self.steps,
                               {"bucket": bucket, "size": len(batch)})
            self._run_batch(bucket, batch)
        self.steps += 1
        self._c_steps.inc()
        self._g_queue.set(len(self.queue))
        return len(self.completed) - before

    def _batch_array(self, bucket: int, reqs: list[DetRequest]) -> Any:
        images = np.zeros((self.scfg.slots, bucket, bucket, 3), np.float32)
        for i, r in enumerate(reqs):
            arr = np.asarray(r.image, np.float32)
            images[i, :arr.shape[0], :arr.shape[1], :] = arr
        return jnp.asarray(images)

    def _program(self, bucket: int, rung: str, cfg: R.ResNetDCNConfig):
        """The jitted forward of one (bucket, rung) and the names of the
        taps it returns: ``fwd(params, images, scales)``, with the
        config closed over."""
        # Keyed on the model's forward as well: one replaced at run time
        # (a fault a test injects) is traced afresh.
        key = (bucket, rung, R.forward)
        if key in self._programs:
            return self._programs[key]
        names: list[str] = []

        def forward(params, x, scales):
            taps: list = []
            out, _ = R.forward(
                params, cfg, x, quant_scales=scales,
                tap=None if self.tap is None
                else lambda n, a: taps.append((n, a)))
            names[:] = [n for n, _ in taps]
            # Counted once the trace is through: one aborted by a
            # kernel failure or a dispatch hook is the ladder's to count.
            self._c_traces.inc(bucket=str(bucket), rung=rung)
            return {"cls": out["cls"], "box": out["box"],
                    "taps": [a for _, a in taps]}

        self._programs[key] = (jax.jit(forward), names)
        return self._programs[key]

    def _forward(self, rung: str, x, bucket: int):
        cfg = self._cfgs[rung]
        # Spatial buckets (ISSUE 10): the kernel rungs run height-
        # sharded under the bucket's mesh; the chained rung never gets
        # here for them (_run_batch enters the ladder at "int8") and
        # the reference rung has no shard_map wrap.
        shards = self.scfg.spatial_shards_for(bucket)
        spatial = shards > 1 and rung in ("int8", "fp32_kernel")
        if spatial:
            cfg = dataclasses.replace(cfg, shard_spatial=True)
        mesh_ctx = use_rules(mesh=self._spatial_meshes[bucket]) \
            if spatial else contextlib.nullcontext()
        fwd, names = self._program(bucket, rung, cfg)
        # The first call traces inside this scope: a kernel failure
        # raises here instead of baking ops' reference fallback into
        # the program.
        with mesh_ctx, ops.degradation_scope(False):
            try:
                out = fwd(self.params, x, self._scales)
            except Exception:
                # JAX retraces a function on every later call once one
                # of its traces has raised: the next attempt builds the
                # program from a fresh function.
                del self._programs[(bucket, rung, R.forward)]
                raise
        for name, a in zip(names, out["taps"]):
            self.tap(name, a)
        return out

    def _run_batch(self, bucket: int, reqs: list[DetRequest]) -> None:
        with self._tr.span("serve/batch", bucket=bucket, size=len(reqs)):
            x = self._batch_array(bucket, reqs)
        rung_idx = LADDER.index(self.scfg.quant)
        if self.scfg.spatial_shards_for(bucket) > 1 \
                and LADDER[rung_idx] == "int8_chain":
            # Chained int8 cannot halo-split its fused offset stage;
            # spatial buckets enter the ladder one rung down.
            rung_idx = LADDER.index("int8")
        attempt = 0
        while True:
            try:
                with self._tr.span("serve/forward", rung=LADDER[rung_idx]):
                    out = self._forward(LADDER[rung_idx], x, bucket)
                # The forward only enqueues: a device failure surfaces
                # when the results are read, and takes the ladder too.
                with self._tr.span("serve/fetch"):
                    cls = np.asarray(out["cls"])
                    box = np.asarray(out["box"])
                break
            except Exception as e:          # noqa: BLE001 — typed below
                self._c_retries.inc()
                self._tr.event("serve/retry", bucket=bucket,
                               rung=LADDER[rung_idx], attempt=attempt + 1)
                for r in reqs:
                    r.retries += 1
                attempt += 1
                if attempt <= self.scfg.max_retries:
                    # transient: replay the affected slots, same rung
                    if self.scfg.retry_backoff:
                        self._sleep(self.scfg.retry_backoff
                                    * 2 ** (attempt - 1))
                    continue
                if rung_idx + 1 < len(LADDER):
                    # persistent: drop one rung, fresh retry budget
                    rung_idx += 1
                    attempt = 0
                    for r in reqs:
                        r.degraded = True
                    self._c_degraded.inc()
                    self._tr.event("serve/degrade", bucket=bucket,
                                   rung=LADDER[rung_idx])
                    continue
                for r in reqs:              # bottom rung failed: typed
                    self._retire(r, "failed",
                                 f"{type(e).__name__}: {e}")
                return
        now = self.clock()
        with self._tr.span("serve/retire", size=len(reqs)):
            for i, r in enumerate(reqs):
                r.ladder = LADDER[rung_idx]
                self._c_ladder.inc(rung=r.ladder)
                if r.deadline is not None and now > r.deadline:
                    self._retire(r, "deadline_exceeded",
                                 f"completed {now - r.deadline:.3f}s past "
                                 f"deadline (result dropped)")
                    continue
                r.result = {"cls": cls[i], "box": box[i]}
                self._retire(r, "ok")

    def run_until_drained(self, max_steps: int = 10_000
                          ) -> list[DetRequest]:
        steps = 0
        while len(self.queue) and steps < max_steps:
            self.step()
            steps += 1
        return self.completed

    # -- telemetry -----------------------------------------------------
    def telemetry(self) -> dict:
        """Per-request records + engine counters — the schema
        ``resilience.dump_telemetry`` writes (docs/serving.md)."""
        per_bucket: dict[str, int] = {}
        for r in self.completed:
            if r.outcome == "ok":
                key = str(r.bucket)
                per_bucket[key] = per_bucket.get(key, 0) + 1
        return {
            "engine": {
                "buckets": list(self.scfg.buckets),
                "slots": self.scfg.slots,
                "quant": self.scfg.quant,
                "strict_buckets": self.scfg.strict_buckets,
                "queue_capacity": self.scfg.queue_capacity,
                "shed_policy": self.scfg.shed_policy,
                "batch_window": self.scfg.batch_window,
                "spatial_shards": [list(e)
                                   for e in self.scfg.spatial_shards],
            },
            "steps": self.steps,
            "counters": dict(self.counters),
            "served_per_bucket": per_bucket,
            "plan_cache": plan.tile_cache_info(),
            "plans": {str(b): {k: list(v) for k, v in p.items()}
                      for b, p in self.plans.items()},
            "plan_sources": {str(b): dict(s)
                             for b, s in self.plan_sources.items()},
            "requests": [{
                "uid": r.uid, "outcome": r.outcome, "bucket": r.bucket,
                "ladder": r.ladder, "degraded": r.degraded,
                "retries": r.retries, "latency_s": r.latency_s(),
                "error": r.error,
            } for r in self.completed],
            "metrics": self.metrics.snapshot(),
        }
