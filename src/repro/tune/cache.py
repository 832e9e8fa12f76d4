"""Versioned tuned-tile cache (ISSUE 9).

The measured-time autotuner (``repro.tune.autotune``) persists its
winners here: one JSON file under ``bench-out/`` mapping a canonical
per-(op, shape, dtype/quant, cores, platform) key to the measured-best
tile geometry + dw-flush cadence.  ``kernels.plan.resolve_tiles``
consults the *installed* cache before the analytic Sec. 3.2 chooser —
so the dispatcher, the Trainer, and the serving engine's bucket plan
warming all read tuned tiles with zero call-site changes.

Resilience contract (the warn-once idiom of ``repro.resilience``): a
missing cache file is COLD (silent analytic fallback — the normal
state of a fresh checkout); a corrupt or version-incompatible file
falls back to the analytic chooser with exactly one warning per path on
the ``repro.tune`` logger.  Platform keys (``launch.platform``) keep
interpret-mode wall-time winners from ever being served under Mosaic
or the XLA reference lowering.

Installing a cache invalidates the memoized tile resolution and the
jit trace caches — ``resolve_tiles`` runs at trace time, so traces
built before the install would otherwise keep their analytic tiles.
Install before building engines/Trainers to avoid paying that
recompile.
"""
from __future__ import annotations

import contextlib
import json
import logging
import os

CACHE_VERSION = 1

# Canonical on-disk location (relative to the repo root / bench cwd) —
# what ``benchmarks/run.py --tune`` writes and CI uploads.
DEFAULT_CACHE_PATH = os.path.join("bench-out", "TUNED_tiles.json")

_log = logging.getLogger("repro.tune")

_WARNED: set = set()


class TileCacheError(RuntimeError):
    """A cache file exists but cannot be served (corrupt JSON, wrong
    schema, incompatible version)."""


def reset_cache_warnings() -> None:
    """Forget which cache paths / entries already warned (tests)."""
    _WARNED.clear()


def warn_once(key, msg: str, *args) -> None:
    """Warn exactly once per ``key`` on the ``repro.tune`` logger —
    the same warn-once idiom as ``ops``'s degradation fallback."""
    if key in _WARNED:
        return
    _WARNED.add(key)
    _log.warning(msg, *args)


def entry_key(*, h: int, w: int, c: int, m: int, kernel_size: int = 3,
              stride: int = 1, dilation: int = 1, offset_bound: float,
              objective: str, dtype: str | None, cores: int,
              platform: str) -> str:
    """Canonical string key of one tuned entry.

    The fields are exactly the signature of
    ``kernels.plan.resolve_tiles`` plus the lowering platform — batch
    is deliberately NOT part of the key (``resolve_tiles`` never sees
    it; the tuner records its measurement batch inside the entry
    instead).  ``objective`` doubles as the op discriminator
    ("training" = the fwd+bwd deform_conv dispatch, "forward" = the
    inference/serving resolution), ``dtype`` as the quant discriminator
    (None = fp32 datapath, "int8" = quantized band).
    """
    return (f"dcl/{h}x{w}x{c}->{m}/k{kernel_size}s{stride}d{dilation}"
            f"/B{float(offset_bound):g}/{objective}/{dtype or 'fp32'}"
            f"/cores{cores}/{platform}")


class TileCache:
    """In-memory view of one versioned tuned-tile cache file.

    ``entries`` maps :func:`entry_key` strings to plain dicts — at
    minimum ``{"tiles": [th, tw, tc, tm]}``, typically also
    ``dw_flush_every_step``, ``cores``, ``recommended_cores``,
    ``measured_us``, ``analytic_us``, ``analytic_tiles``, ``batch``,
    ``reps``.  Consumers (``plan.resolve_tiles``) validate entries at
    lookup time and fall back to the analytic chooser on anything
    malformed — a stale cache can cost a warning, never a crash.
    """

    def __init__(self, entries: dict | None = None, *,
                 path: str | None = None):
        self.entries: dict[str, dict] = dict(entries or {})
        self.path = path

    def __len__(self) -> int:
        return len(self.entries)

    def lookup(self, **key_fields) -> dict | None:
        """The tuned entry for one resolution key, or None (cold)."""
        return self.entries.get(entry_key(**key_fields))

    def put(self, entry: dict, **key_fields) -> str:
        """Store ``entry`` under the canonical key; returns the key."""
        key = entry_key(**key_fields)
        self.entries[key] = dict(entry)
        return key

    def save(self, path: str | None = None) -> str:
        """Write the versioned JSON file (creating the directory)."""
        path = path or self.path or DEFAULT_CACHE_PATH
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        payload = {
            "version": CACHE_VERSION,
            "note": "measured-time autotuner winners (repro.tune) — "
                    "keys are per-(shape, objective, dtype, cores, "
                    "platform); see docs/autotuning.md",
            "entries": self.entries,
        }
        with open(path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        self.path = path
        return path

    @classmethod
    def load(cls, path: str) -> "TileCache":
        """Parse a cache file; raises :class:`TileCacheError` on corrupt
        JSON, a non-dict schema, or a version mismatch (the loader
        never guesses across versions — re-tune instead)."""
        try:
            with open(path) as f:
                payload = json.load(f)
        except (OSError, ValueError) as e:
            raise TileCacheError(
                f"tuned-tile cache {path!r} is unreadable "
                f"({type(e).__name__}: {e})") from e
        if not isinstance(payload, dict) \
                or not isinstance(payload.get("entries"), dict):
            raise TileCacheError(
                f"tuned-tile cache {path!r} has no 'entries' mapping")
        version = payload.get("version")
        if version != CACHE_VERSION:
            raise TileCacheError(
                f"tuned-tile cache {path!r} is version {version!r}; this "
                f"build reads version {CACHE_VERSION} — re-run the tuner "
                f"(benchmarks/run.py --tune)")
        return cls(payload["entries"], path=path)


# ---------------------------------------------------------------------------
# Process-global active cache (what ``plan.resolve_tiles`` consults).
# ---------------------------------------------------------------------------

_active: TileCache | None = None
_load_errors = 0


def load_tile_cache(path: str) -> TileCache | None:
    """Load a cache file with the resilience contract: a missing file
    is cold (None, silent); a corrupt/incompatible file is None with a
    single warning per path on the ``repro.tune`` logger — the caller
    falls back to the analytic chooser either way."""
    global _load_errors
    if not os.path.exists(path):
        return None
    try:
        return TileCache.load(path)
    except TileCacheError as e:
        _load_errors += 1
        warn_once(("load", os.path.abspath(path)),
                  "%s; falling back to the analytic tile chooser "
                  "(warned once per path)", e)
        return None


def install_tile_cache(cache) -> TileCache | None:
    """Install (or clear, with None) the process-global tuned cache;
    returns the previous one.  Accepts a :class:`TileCache` or a path
    (loaded via :func:`load_tile_cache` — corrupt files install None).

    Installing invalidates ``plan.resolve_tiles``'s memoization and the
    jit trace caches: tile resolution happens at trace time, so traces
    built against the previous cache would otherwise survive the
    switch.
    """
    global _active
    if isinstance(cache, (str, os.PathLike)):
        cache = load_tile_cache(os.fspath(cache))
    prev, _active = _active, cache
    try:
        from repro.kernels.plan import resolve_tiles
        resolve_tiles.cache_clear()
    except Exception:  # noqa: BLE001
        pass
    import jax
    jax.clear_caches()
    return prev


def active_tile_cache() -> TileCache | None:
    """The installed tuned-tile cache (None when cold/analytic)."""
    return _active


@contextlib.contextmanager
def tile_cache_scope(cache):
    """Scoped :func:`install_tile_cache` with guaranteed restore."""
    prev = install_tile_cache(cache)
    try:
        yield
    finally:
        install_tile_cache(prev)


def cache_info() -> dict:
    """Status of the installed cache — merged into
    ``plan.tile_cache_info`` (and from there the serving engine's
    telemetry) so a cold or corrupt cache is visible, not silent."""
    return {
        "installed": _active is not None,
        "entries": len(_active) if _active is not None else 0,
        "path": getattr(_active, "path", None),
        "load_errors": _load_errors,
    }
