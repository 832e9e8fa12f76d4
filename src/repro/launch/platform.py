"""Lowering-platform switch for the bounded-kernel emitter (ISSUE 9).

Every bounded kernel is emitted from the one ``band_pipeline``
abstraction, so the repo has exactly three ways to lower a dispatch:

* ``"tpu"``      — Mosaic lowering of the Pallas kernels (real TPU
  backend required; the container only dry-runs this).
* ``"interpret"`` — Pallas interpret mode (the CPU default of this
  container: the kernel's grid loop runs in Python, wall times are a
  scaling signal only).
* ``"xla_ref"``  — pure-XLA reference lowering: ``ops.deform_conv`` /
  ``ops.deform_conv_chain`` dispatch the ``ref.py`` / fake-quant
  reference forms of the same arithmetic instead of emitting a Pallas
  kernel at all.  This is the degradation ladder's bottom rung promoted
  to a first-class backend — the parity baseline the tuner and the
  test-suite compare the emitted kernels against.

The idiom follows SNIPPETS Snippet 1 (``set_platform``): one
process-global switch, consulted at dispatch time by
``ops.default_interpret`` and keyed into the tuned-tile cache
(``repro.tune``) so measured winners never leak across backends — an
interpret-mode wall-time winner says nothing about Mosaic.

Switching platforms invalidates both tile-resolution memoization
(``kernels.plan.resolve_tiles`` — its results now depend on the
platform-keyed tuned cache) and the jit trace caches (``interpret`` is
a static baked at trace time), so a switch mid-process cannot serve a
stale lowering.
"""
from __future__ import annotations

import contextlib
import os
import pathlib

PLATFORMS = ("tpu", "interpret", "xla_ref")

_platform: str | None = None        # None -> default_platform()


def default_platform() -> str:
    """The platform this process lowers to when none is set: Mosaic on
    a real TPU backend, Pallas interpret mode everywhere else."""
    import jax
    return "tpu" if jax.default_backend() == "tpu" else "interpret"


def current_platform() -> str:
    """The active lowering platform — the tuned-tile cache key
    component (``repro.tune.cache``) and the ``ops.default_interpret``
    source of truth."""
    return _platform if _platform is not None else default_platform()


def _invalidate_lowering_caches() -> None:
    """Drop every cache that baked the previous platform: the memoized
    tile resolution (tuned entries are platform-keyed) and the jit
    traces (``interpret`` is a static argument resolved at trace
    time)."""
    try:
        from repro.kernels.plan import resolve_tiles
        resolve_tiles.cache_clear()
    except Exception:  # noqa: BLE001 — plan not importable yet is fine
        pass
    import jax
    jax.clear_caches()


def set_platform(name: str) -> str:
    """Select the lowering platform; returns the previously active one
    (the save/restore value ``platform_scope`` uses).

    ``"tpu"`` is only valid when the jax backend actually is a TPU —
    selecting Mosaic lowering on a CPU container would fail deep inside
    Pallas; the friendly error here is the co-design guard.
    """
    global _platform
    if name not in PLATFORMS:
        raise ValueError(
            f"unknown platform {name!r}; expected one of {PLATFORMS} "
            f"(see docs/autotuning.md)")
    import jax
    if name == "tpu" and jax.default_backend() != "tpu":
        raise ValueError(
            f"platform='tpu' selects Mosaic lowering, but the jax "
            f"backend is {jax.default_backend()!r} — run on a TPU host "
            f"or pick 'interpret' / 'xla_ref'")
    prev = current_platform()
    _platform = name
    if name != prev:
        _invalidate_lowering_caches()
    return prev


@contextlib.contextmanager
def platform_scope(name: str):
    """Scoped :func:`set_platform` with guaranteed restore — what the
    parity suite and the tuner use to compare lowerings without leaking
    the switch into unrelated callers."""
    prev = set_platform(name)
    try:
        yield
    finally:
        set_platform(prev)


# The checkout's own compile-cache directory (listed in .gitignore): a
# fixed path, so a rerun from the same checkout finds its entries.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory: ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets
    it, else ``REPO_CACHE_DIR``.  Called by the launchers and
    ``chip_smoke.py`` before their first compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_summary() -> dict:
    """The device this process runs on, as JAX reports it: platform,
    device kind and device count — printed by every launcher so no
    result is read against the wrong device."""
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}
