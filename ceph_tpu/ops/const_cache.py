"""Device-resident constants of the launch path.

A fused launch needs a handful of arrays that do not depend on the
data: the crc tile / advance / combine matrices of its operating point
and the small index maps of its run layout.  Their numpy side is
`lru_cache`d where it is built; this is the device side — each is
uploaded once per (what it depends on, backend) and the SAME device
array is handed to every later launch, so a steady-state launch
uploads its staged data and nothing else.

Safe under the launch thread and the prewarm threads (one lock, held
across the upload so a constant is uploaded exactly once).  Bounded:
least-recently-used entries go first, and the per-launch matrices are
touched by every launch, so only stale run layouts ever leave.  A
cached array is an INPUT of every launch that uses it and must never
be donated (`donate_argnums` names the staged words only).
"""

from __future__ import annotations

import threading
from collections import OrderedDict

import jax

MAX_ENTRIES = 64     # the largest entry is 512 KiB (crc_tile_matrix_w32)

_lock = threading.Lock()
_cache: OrderedDict[tuple, object] = OrderedDict()


class Tally:
    """What one launch took from the cache: hits, misses, and the
    bytes the misses uploaded."""

    __slots__ = ("hits", "misses", "nbytes")

    def __init__(self):
        self.hits = self.misses = self.nbytes = 0


def get(key: tuple, build, tally: Tally | None = None):
    """The device array of constant `key` on the current backend;
    `build()` makes its numpy value on a miss.  `tally` (one launch's
    own) is counted here, where the upload happens."""
    full = (jax.default_backend(),) + key
    with _lock:
        arr = _cache.get(full)
        hit = arr is not None and not arr.is_deleted()
        if hit:
            _cache.move_to_end(full)
        else:
            # concrete even when asked for under a jit trace (the
            # single-extent fold entry builds its launch args there)
            with jax.ensure_compile_time_eval():
                arr = jax.device_put(build())
            _cache[full] = arr
            while len(_cache) > MAX_ENTRIES:
                _cache.popitem(last=False)
    if tally is not None:
        if hit:
            tally.hits += 1
        else:
            tally.misses += 1
            tally.nbytes += int(arr.nbytes)
    return arr


def reset_for_tests() -> None:
    with _lock:
        _cache.clear()
