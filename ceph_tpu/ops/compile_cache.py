"""Persistent XLA compile cache: one compile per host LIFETIME.

JAX's persistent compilation cache serializes every compiled
executable to disk keyed by the (HLO, compile options, backend)
fingerprint, so a RESTARTED daemon re-traces its jit buckets but never
re-compiles them — the multi-second XLA/Mosaic compile that used to
flap heartbeats on every revive becomes a millisecond disk read.

Placement is decided OUTSIDE the program.  When
`JAX_COMPILATION_CACHE_DIR` is set JAX already uses that directory and
this module sets no other; when it is unset the cache lives at one
fixed path inside the checkout (`.jax_cache/` beside `native/`,
git-ignored).  The path is part of the cache key's neighbourhood — a
directory that moves between runs never hits — so there is no option,
no program-specific environment variable and no temp name.

Hit/miss attribution rides jax.monitoring: the backend records
'/jax/compilation_cache/cache_hits' when a compile is served from
disk, '/jax/compilation_cache/cache_misses' when it was compiled and
stored, and a '/jax/core/compile/backend_compile_duration' duration
around both.  This module keeps process-global counters; the flight
recorder (ops/profiler.py) snapshots the hit counter around each
first-seen submit, so a persistent-cache hit records as a fast
first-launch with `cache_hit: true` in the launch ledger — NOT as a
compile stall — and chip_smoke.py deltas `counters()` around its
serving window to count compilations that happened inside it.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
# resolved from __file__ like common/native.py's library path: the
# same checkout always gets the same directory
CHECKOUT_DIR = Path(__file__).resolve().parent.parent.parent / ".jax_cache"

_lock = threading.Lock()
_enabled = False
_dir: str | None = None
# process-global counters (bumped by the jax.monitoring listeners; int
# reads are atomic under the GIL, so the profiler's per-launch
# snapshots never take _lock)
_hits = 0
_misses = 0
_requests = 0
_compile_s = 0.0


def _on_event(event: str, **kw) -> None:
    global _hits, _misses
    if event == "/jax/compilation_cache/cache_hits":
        _hits += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _misses += 1


def _on_duration(event: str, duration: float, **kw) -> None:
    global _requests, _compile_s
    if event == "/jax/core/compile/backend_compile_duration":
        _requests += 1
        _compile_s += duration


def enable() -> str:
    """Turn the persistent cache on for this process and register the
    listeners; returns the directory in use.  Idempotent.  Must run
    before the first jit COMPILE to cover it, but is safe (and still
    effective for later compiles) at any point."""
    global _enabled, _dir
    with _lock:
        if _enabled:
            return _dir
        import jax
        path = os.environ.get(ENV_DIR)
        if path:
            # placed from outside: jax read the variable at import;
            # setting the config here would override the caller
            if jax.config.jax_compilation_cache_dir != path:
                raise RuntimeError(
                    f"{ENV_DIR}={path!r} was set after jax was "
                    f"imported (jax uses "
                    f"{jax.config.jax_compilation_cache_dir!r})")
        else:
            path = str(CHECKOUT_DIR)
            jax.config.update("jax_compilation_cache_dir", path)
            # jax memoizes "is the cache in use" at the process's
            # FIRST compile; if anything compiled before enable() that
            # latch reads "disabled" forever.  Drop it so the next
            # compile re-evaluates against the directory just set.
            from jax._src import compilation_cache as _jcc
            _jcc.reset_cache()
        Path(path).mkdir(parents=True, exist_ok=True)
        # daemon workloads are many SMALL programs: cache every
        # compile regardless of size or compile time (the defaults
        # skip sub-second compiles — exactly the ones whose sum makes
        # a revive storm)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _enabled = True
        _dir = path
        return path


def hit_count() -> int:
    """Process-global persistent-cache hits (monotonic; the profiler
    deltas it around each submit for per-launch attribution)."""
    return _hits


def counters() -> dict:
    """Monotonic compile counters of this process.

    `requests` and `compile_s` come from jax.monitoring's duration
    event '/jax/core/compile/backend_compile_duration', which jax
    records around the WHOLE of compile_or_get_cached (pxla, both the
    jit and the AOT `.lower().compile()` path): every backend compile
    request adds its wall seconds, whether the persistent cache then
    called it a hit (a disk read plus deserialization — which on
    another machine's cache can itself recompile), a miss (a real
    compile, stored) or was not consulted.  So `compile_s` cannot be
    fooled by a "hit" that still compiles; `hits` / `misses` (the
    events '/jax/compilation_cache/cache_hits' / 'cache_misses') only
    say what the cache called each request."""
    return {"requests": _requests, "hits": _hits, "misses": _misses,
            "compile_s": round(_compile_s, 3)}


def status() -> dict:
    """The `prewarm status` / `compile ledger` asok block."""
    out = {"enabled": _enabled, "dir": _dir,
           "placed_by": "env" if os.environ.get(ENV_DIR) else "checkout",
           **counters()}
    if _enabled:
        try:
            files = [f for f in Path(_dir).iterdir() if f.is_file()]
            out["entries"] = len(files)
            out["bytes"] = sum(f.stat().st_size for f in files)
        except OSError:     # jax renames entries into place under us
            pass
    return out
