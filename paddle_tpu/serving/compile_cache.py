"""Persistent compile cache for the serving engine (ISSUE 16).

`ContinuousBatchingEngine.warm()` compiles the whole program fleet —
the decode chunk, the unified ragged step, and (on the split path) the
prefill zoo. On a fleet restart or an elastic scale-out every replica
pays that compile storm again for byte-identical programs. JAX already
ships the fix — the persistent compilation cache keys compiled
executables by program fingerprint and serves them from disk — this
module wires it to the engine:

- `enable_compile_cache()` is the ONE place that decides where the
  cache lives (`JAX_COMPILATION_CACHE_DIR` from outside, else a fixed
  path in the checkout); the engine calls it at build time, so the
  cache is always on;
- a process-global monitoring listener counts compile requests vs
  cache hits, so `warm()` can report COLD vs WARM compile counts
  (`engine.warm_compile_stats`, surfaced through `metrics()`): a
  second process warming the same engine off the same cache dir must
  report zero misses — the scriptable "no compile storm" gate;
- the tuned-config artifact (`analysis/tuner.py`,
  `.paddle_tpu_tune.json`) is designed to live IN the cache dir, so
  the tuned knobs and the executables they compiled travel together.

The listener rides jax's internal monitoring events
(``/jax/compilation_cache/*``) of the one installed jax.
"""
from __future__ import annotations

import os
from typing import Optional

__all__ = [
    "DEFAULT_CACHE_DIR", "cache_dir", "enable_compile_cache", "snapshot",
    "stats_since",
]

# compile-request / cache-hit counts since process start, fed by the
# one registered monitoring listener
_COUNTS = {"requests": 0, "hits": 0}
_LISTENING = False
_CACHE_DIR: Optional[str] = None

_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _listener(event, **kwargs):
    if event == _REQUEST_EVENT:
        _COUNTS["requests"] += 1
    elif event == _HIT_EVENT:
        _COUNTS["hits"] += 1


def _ensure_listener() -> None:
    """Register the monitoring listener once."""
    global _LISTENING
    if not _LISTENING:
        from jax._src import monitoring

        monitoring.register_event_listener(_listener)
        _LISTENING = True


# where the cache lives when nobody outside placed it: a fixed path in
# the checkout (the path is part of jax's cache key — a directory that
# moves between runs never hits). Listed in .gitignore.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache(directory: Optional[str] = None) -> str:
    """THE decision where the persistent compilation cache lives —
    the engine, `chip_smoke.py` and the bench scripts all come through
    here, and the cache is always on:

    - `JAX_COMPILATION_CACHE_DIR` set: jax itself reads it; this
      function sets no directory in code, and neither `directory` nor
      FLAGS_compile_cache may override it.
    - else an explicit `directory`, else the one an earlier call in
      this process chose, else FLAGS_compile_cache /
      PADDLE_TPU_COMPILE_CACHE, else `DEFAULT_CACHE_DIR`.

    Returns the directory in force. The min-compile-time and
    min-entry-size floors are zeroed when a directory is first chosen,
    so EVERY engine program persists: the fleet-restart win is the
    whole warm() zoo, and tiny CI-model programs must exercise the
    same path the 70B fleet relies on."""
    global _CACHE_DIR
    import jax

    _ensure_listener()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        if _CACHE_DIR != placed:
            _zero_floors()
            _CACHE_DIR = placed
        return placed
    if directory is None:
        if _CACHE_DIR is not None:
            return _CACHE_DIR
        from ..framework.flags import flag

        directory = str(flag("compile_cache") or "") or DEFAULT_CACHE_DIR
    directory = os.path.abspath(str(directory))
    os.makedirs(directory, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", directory)
    _zero_floors()
    # jax latches its is-the-cache-on decision at the FIRST compile of
    # the process; any jax op before this call (model init, engine
    # pools) leaves the latch stuck on "disabled" — reads then consult
    # a None cache and writes silently no-op. Reset so the next
    # compile re-initializes against the directory just configured.
    from jax._src import compilation_cache as _jcc

    cache = getattr(_jcc, "_cache", None)      # not is_initialized
    if cache is None or str(getattr(cache, "_path", "")) != directory:
        _jcc.reset_cache()
    _CACHE_DIR = directory
    return directory


def _zero_floors() -> None:
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def cache_dir() -> Optional[str]:
    """The cache directory in force, or None before the first
    `enable_compile_cache` of this process."""
    return _CACHE_DIR


def snapshot() -> dict:
    """Current cumulative counter values — take one before a compile
    burst, hand it to `stats_since` after."""
    _ensure_listener()
    return dict(_COUNTS)


def stats_since(snap: dict) -> dict:
    """Compile-cache traffic since `snap`: requests that consulted the
    persistent cache, hits served from it, and misses (fresh
    compilations that wrote new entries). With the cache disabled jax
    emits no events, so all three read 0 — `persistent_cache_dir`
    (None) and `counters_available` disambiguate "no compiles" from
    "not measured"."""
    return {
        "persistent_cache_dir": _CACHE_DIR,
        "counters_available": _LISTENING,
        "compile_requests": _COUNTS["requests"] - snap.get("requests", 0),
        "cache_hits": _COUNTS["hits"] - snap.get("hits", 0),
        "cache_misses": (_COUNTS["requests"] - snap.get("requests", 0))
        - (_COUNTS["hits"] - snap.get("hits", 0)),
    }
