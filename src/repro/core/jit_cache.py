"""Process-wide memo for jit/lower artifacts — the ROADMAP's Session-level
caching item (docs/performance.md).

Every `Session.train` used to rebuild and re-trace its train step, and
every `Session.serve` call re-jitted the decode step, even when nothing
that shapes the traced computation had changed. This module keys the built
artifacts on the *values* that reach the trace — the `ModelConfig`, the
`RunConfig` fields the step closure reads, the mesh and the sharding
rules — so repeated train/serve calls (and fresh Sessions over the same
config) reuse one jitted callable, and XLA's own compilation cache is hit
instead of rebuilt.

Keys are `repr()` strings of plain dataclasses/tuples: a faithful value
key for the frozen config objects used here, with the fields that never
enter the traced graph (checkpoint paths, data seeds, checkpoint cadence)
normalized away by the callers.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, Iterable, Tuple, TypeVar

T = TypeVar("T")

_CACHE: Dict[Tuple[str, str], Any] = {}
_HITS = 0
_MISSES = 0


def cache_key(*parts: object) -> str:
    """A stable value-key from reprs of config-shaped objects."""
    return "|".join(repr(p) for p in parts)


def cached(kind: str, key_parts: Iterable[object],
           build: Callable[[], T]) -> T:
    """Return the memoized artifact for (kind, key), building it once."""
    global _HITS, _MISSES
    key = (kind, cache_key(*key_parts))
    if key in _CACHE:
        _HITS += 1
    else:
        _MISSES += 1
        _CACHE[key] = build()
    return _CACHE[key]


def stats() -> Dict[str, int]:
    return {"entries": len(_CACHE), "hits": _HITS, "misses": _MISSES}


def clear() -> None:
    """Drop all cached artifacts (tests; frees tracer memory)."""
    global _HITS, _MISSES
    _CACHE.clear()
    _HITS = _MISSES = 0


def normalized_run(run) -> object:
    """A RunConfig with the trace-irrelevant fields zeroed, for keying:
    checkpoint_dir/interval steer the outer loop, seed steers data,
    resilience policies steer retries around the step — none of them
    reach the jitted step function."""
    return dataclasses.replace(run, checkpoint_dir="",
                               checkpoint_interval=0, seed=0,
                               resilience=None, recalibration=None)


def place_compilation_cache(root: str) -> None:
    """Keep JAX's persistent compilation cache at `<root>/.jax_cache`.

    Called by the entry points (`python -m repro`, `chip_smoke.py`), never
    at import. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself
    and this leaves it alone. The directory is fixed, not per run: a cache
    that moves is never hit again. JAX's default thresholds decide what is
    written there: compiles faster than `jax_persistent_cache_min_compile_
    time_secs` (1 s) are not kept."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(os.path.abspath(root), ".jax_cache"))
