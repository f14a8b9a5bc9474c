"""Segment arenas: the buffers a read's segment is gathered, assembled
and joined in, leased from one process-wide pool and given back by the
garbage collector.

A GET's chunk is built on a pool thread, sent by the request thread and
alive while the next segment is built on another thread; `bucket/tier.py`
joins every chunk of an object before it lets one go.  So a buffer has
no owning thread and its end is no protocol a consumer keeps: it ends
when the last view of it dies.  `lease` hands out ONE ndarray over an
arena; every later reshape, slice and memoryview keeps that array alive
(numpy collapses a view's `.base` to the first ndarray of the chain, so
the arena itself is an anonymous `mmap`, never an ndarray), and a
`weakref.finalize` on it puts the arena back on the free list, as
`hotcache.lookup_view` releases its run.  Nothing is ever written under
a live view: a consumer that keeps a chunk keeps its arena, and the pool
maps another.

Why a pool at all: a fresh 32 MiB mapping costs ~10 us a page at first
touch under load (8,192 pages a copy) and a fault holds the address
space against every other thread (PERF.md, PR 34-40).
"""

from __future__ import annotations

import bisect
import collections
import itertools
import mmap
import os
import threading
import weakref

import numpy as np

from ..observe.metrics import DATA_PATH

# Bytes the free list keeps; what comes back beyond them is unmapped,
# the longest unused first.  Eight streams at EC:6+6, each with two
# segments in flight that hold the shard rows read, an `x`, a `y` and a
# join of 33.5 MB, peak at ~2.1 GB (PERF.md §6); 3 GiB covers that.
FREE_CAP_BYTES = 3 << 30
# Below this a buffer is not leased but allocated: under malloc's mmap
# threshold (128 KiB unless it has grown) a fresh array never was a
# mapping, and an arena is whole pages, which a pool of ranged reads'
# few hundred bytes each would hold by the million.
MIN_LEASE_BYTES = 128 << 10


class SegmentArenas:
    """A free list of anonymous mappings, smallest fit first."""

    def __init__(self, cap_bytes: int = FREE_CAP_BYTES):
        self.cap_bytes = cap_bytes
        self._reset()

    def _reset(self) -> None:
        self._mu = threading.Lock()
        # The free arenas as (size, age, arena), sorted: the smallest
        # fit is a bisect away, and among arenas of one size the one
        # that came back last (its age counts down) is taken first, so
        # what a quieter hour no longer needs grows old and can go.
        self._free: list[tuple[int, int, mmap.mmap]] = []
        self._free_bytes = 0
        self._age = itertools.count(0, -1)
        # A finalizer runs on any thread at any point, the leasing
        # thread inside `lease` included: it appends here (atomic, no
        # lock) and files the arena only if nobody holds `_mu`.
        self._returned: collections.deque = collections.deque()

    def lease(self, nbytes: int, site: str) -> np.ndarray:
        """A writable 1-D uint8 array of exactly `nbytes`, not zeroed,
        over the smallest free arena that holds it and is at most twice
        as long (a 10 MiB read never pins a 33 MiB arena), else over a
        new one; under MIN_LEASE_BYTES a plain new array.  What had to
        be allocated is counted as fresh at `site`
        (mtpu_get_fresh_buffer_bytes_total), reuse as leased
        (mtpu_get_leased_buffer_bytes_total)."""
        if nbytes < MIN_LEASE_BYTES:
            DATA_PATH.record_get_fresh_buffer(site, nbytes)
            return np.empty(max(nbytes, 0), dtype=np.uint8)
        arena = None
        with self._mu:
            self._file_returned()
            i = bisect.bisect_left(self._free, (nbytes,))
            if i < len(self._free) and self._free[i][0] <= 2 * nbytes:
                size, _, arena = self._free.pop(i)
                self._free_bytes -= size
        if arena is None:
            arena = mmap.mmap(-1, nbytes,
                              flags=mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS)
            DATA_PATH.record_get_fresh_buffer(site, nbytes)
        else:
            DATA_PATH.record_get_leased_buffer(site, nbytes)
        base = np.frombuffer(arena, dtype=np.uint8, count=nbytes)
        weakref.finalize(base, self._give_back, arena).atexit = False
        return base

    def _give_back(self, arena: mmap.mmap) -> None:
        self._returned.append(arena)
        if self._mu.acquire(blocking=False):
            try:
                self._file_returned()
            finally:
                self._mu.release()

    def _file_returned(self) -> None:
        """Move what the finalizers brought onto the free list, making
        room under the cap first: the longest unused go (`_mu` held).
        Dropped, not closed: the array that just died may still hold
        its export, and a mapping goes with its last one."""
        while self._returned:
            arena = self._returned.popleft()
            size = len(arena)
            if size > self.cap_bytes:
                continue
            while self._free_bytes + size > self.cap_bytes:
                oldest = max(self._free, key=lambda e: e[1])
                self._free.remove(oldest)
                self._free_bytes -= oldest[0]
            bisect.insort(self._free, (size, next(self._age), arena))
            self._free_bytes += size

    def free_bytes(self) -> int:
        """Bytes on the free list (mtpu_get_arena_free_bytes)."""
        with self._mu:
            self._file_returned()
            return self._free_bytes


POOL = SegmentArenas()


def lease(nbytes: int, site: str) -> np.ndarray:
    """`SegmentArenas.lease` of the process's pool."""
    return POOL.lease(nbytes, site)


# A forked worker starts with no arena and a lock nobody holds (the
# mappings are private: what it drops, its parent keeps).
os.register_at_fork(after_in_child=lambda: POOL._reset())
