"""Device-resident shard cache + host<->device boundary accounting.

Every dispatch uploads its shard batch across the host<->device
boundary, whose cost is not yet measured on this machine.  This module
is the residency half of keeping bytes from crossing twice: verified
(nb, K, S) shard batches from
healthy GETs are kept keyed by `(owner, bucket, object, part, range)`
and guarded by the same `_mark_dirty` generation discipline as the PR 14
hot-object cache, so a re-read (healthy verify, hedged retry, heal) of a
resident range performs ZERO uploads — the bytes either serve straight
from the verified host copy or dispatch against the already-placed
device array.

Fill discipline (mirrors engine/hotcache.py): only a fully-verified
healthy fast-path read may fill — degraded reads, decode fallbacks, and
anything that tripped a digest mismatch never populate the cache — and
the generation is captured BEFORE the shard reads, so a racing write
invalidates the fill rather than the fill masking the write.  A process
restart (crash recovery, pre-fork worker respawn) starts from an empty
cache and fresh owner tokens, so stale generations can never survive a
boot.

The same module owns the process-wide H2D boundary ledger: every
host->device byte crossing (`fused._placed`, `devices.put`, the
coalescer lanes' pipelined staging uploads) is recorded here, per lane,
so benches and tests can assert bytes-crossing-per-byte-served ~= 1.0 on
first touch and ~0 on cache hits without a chip attached.  And the way
back: `start_fetch` / `fetch` are the one place a dispatch kernel's
result crosses to the host (begun at the launch, counted at the fetch),
and `note_result_copies` counts what a kernel copied after it.

Env (read per call so tests flip them without re-importing):

- MTPU_DEVCACHE=0 disables the cache — the byte-identical direct-read
  oracle the differential tests diff against;
- MTPU_DEVCACHE_MB caps resident payload bytes (default 64);
- MTPU_H2D_PIPELINE=0 disables the lanes' pinned-staging double-buffered
  upload pipeline (ops/coalesce.py) — the serial-upload oracle.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict

import numpy as np


def enabled() -> bool:
    return os.environ.get("MTPU_DEVCACHE", "1") != "0"


def cache_bytes() -> int:
    try:
        mb = int(os.environ.get("MTPU_DEVCACHE_MB", "64"))
    except ValueError:
        mb = 64
    return max(1, mb) << 20


def h2d_pipeline_enabled() -> bool:
    return os.environ.get("MTPU_H2D_PIPELINE", "1") != "0"


# -- H2D boundary ledger ------------------------------------------------------

_H2D_MU = threading.Lock()
_H2D_BYTES = 0
_H2D_DISPATCHES = 0
_H2D_LANES: dict[int, dict] = {}
_D2H_BYTES = 0
_D2H_FETCHES = 0
_D2H_EARLY_STARTS = 0
_RESULT_COPY_BYTES = 0


def note_h2d(nbytes: int, device: int | None = None) -> None:
    """Record one host->device crossing of `nbytes` bytes.  Called by
    every upload site (fused._placed, devices.put, the lanes' staged
    device_put) — and by nothing else, so the ledger IS the boundary."""
    global _H2D_BYTES, _H2D_DISPATCHES
    with _H2D_MU:
        _H2D_BYTES += int(nbytes)
        _H2D_DISPATCHES += 1
        if device is not None:
            lane = _H2D_LANES.setdefault(
                int(device), {"h2d_bytes": 0, "h2d_dispatches": 0})
            lane["h2d_bytes"] += int(nbytes)
            lane["h2d_dispatches"] += 1


def start_fetch(arrs) -> None:
    """Ask the runtime to begin bringing the device arrays `arrs` back
    now, while whoever launched them does something else: `fetch` then
    finds the bytes on the host, or waits for what is left of the
    transfer and no more.  The transfers of several arrays run side by
    side on the runtime's threads."""
    global _D2H_EARLY_STARTS
    for a in arrs:
        a.copy_to_host_async()
    with _H2D_MU:
        _D2H_EARLY_STARTS += len(arrs)


def wait_ready(arrs) -> None:
    """Block until the program that writes the device arrays `arrs`
    has ended: after it a `fetch` waits for a transfer alone."""
    for a in arrs:
        a.block_until_ready()


def fetch(arr) -> np.ndarray:
    """A dispatch kernel's result on the host, the crossing counted:
    the whole array comes back, pad rows and all, so the ledger shows
    what a dispatch's shape costs on the way back too.  The array is
    the one the runtime filled: nothing is copied here."""
    global _D2H_BYTES, _D2H_FETCHES
    out = np.asarray(arr)
    with _H2D_MU:
        _D2H_BYTES += out.nbytes
        _D2H_FETCHES += 1
    return out


def _arrays(res):
    """The arrays of a kernel's results, however nested."""
    if isinstance(res, np.ndarray):
        yield res
    elif isinstance(res, (list, tuple)):
        for r in res:
            yield from _arrays(r)


def note_result_copies(fetched: list, results) -> None:
    """Count the bytes of a kernel's `results` that are not views of
    the arrays it `fetched`: what its resolve copied host-to-host after
    the crossing (a restack, a trim that copies).  A result is a view
    of what the runtime filled, or it is counted."""
    global _RESULT_COPY_BYTES
    copied = sum(a.nbytes for a in _arrays(results)
                 if not any(np.may_share_memory(a, f) for f in fetched))
    if copied:
        with _H2D_MU:
            _RESULT_COPY_BYTES += copied


def h2d_stats() -> dict:
    with _H2D_MU:
        return {
            "h2d_bytes": _H2D_BYTES,
            "h2d_dispatches": _H2D_DISPATCHES,
            "d2h_bytes": _D2H_BYTES,
            "d2h_fetches": _D2H_FETCHES,
            "d2h_early_starts": _D2H_EARLY_STARTS,
            "result_copy_bytes": _RESULT_COPY_BYTES,
            "lanes": {d: dict(v) for d, v in sorted(_H2D_LANES.items())},
        }


def reset_h2d() -> None:
    global _H2D_BYTES, _H2D_DISPATCHES, _D2H_BYTES, _D2H_FETCHES, \
        _D2H_EARLY_STARTS, _RESULT_COPY_BYTES
    with _H2D_MU:
        _H2D_BYTES = 0
        _H2D_DISPATCHES = 0
        _D2H_BYTES = 0
        _D2H_FETCHES = 0
        _D2H_EARLY_STARTS = 0
        _RESULT_COPY_BYTES = 0
        _H2D_LANES.clear()


# -- owner tokens + generations ----------------------------------------------

_OWNER_MU = threading.Lock()
_NEXT_OWNER = 0


def next_owner() -> int:
    """Monotonic per-process owner token, one per ErasureSet instance.
    A reopened set (crash recovery, decom re-attach) gets a fresh token,
    so entries filled by the previous incarnation are unreachable — the
    recovery-boot invalidation guarantee without any persisted state."""
    global _NEXT_OWNER
    with _OWNER_MU:
        _NEXT_OWNER += 1
        return _NEXT_OWNER


class Entry:
    """One resident range: the VERIFIED systematic data matrix
    (nb, K, S) for blocks [b0, b1) of one part, plus the (tiny) tail
    fragment when the range covers it.  `host` is the verified numpy
    copy — healthy hits serve from it with zero disk reads, zero
    uploads, zero dispatches, and stay honest under post-fill disk
    corruption (the bytes served are the bytes that passed verify).
    `dev` is the committed jax array, created at fill time when the
    verify dispatch already placed the batch (zero extra upload) or
    lazily on first device consumer otherwise."""

    __slots__ = ("key", "gen", "host", "tail", "dev", "device",
                 "nbytes")

    def __init__(self, key, gen, host, tail, dev, device, nbytes):
        self.key = key
        self.gen = gen
        self.host = host
        self.tail = tail
        self.dev = dev
        self.device = device
        self.nbytes = nbytes


class DeviceShardCache:
    """LRU of verified shard batches, capacity-bounded by payload bytes
    (MTPU_DEVCACHE_MB).  All staleness is generational: `note_mutation`
    bumps `(owner, bucket)` and every later lookup of an entry filled
    under the old generation reaps it — the exact `_mark_dirty` ride the
    PR 14 hot cache uses, one layer down."""

    def __init__(self):
        self._mu = threading.Lock()
        self._entries: "OrderedDict[tuple, Entry]" = OrderedDict()
        self._gen: dict[tuple, int] = {}
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        self.fills = 0
        self.evictions = 0
        self.invalidations = 0
        self.stale_drops = 0
        self.rejects = 0

    # -- generations ---------------------------------------------------------

    def current_gen(self, owner: int, bucket: str) -> int:
        with self._mu:
            return self._gen.get((owner, bucket), 0)

    def note_mutation(self, owner: int, bucket: str) -> None:
        with self._mu:
            self._gen[(owner, bucket)] = \
                self._gen.get((owner, bucket), 0) + 1
            self.invalidations += 1

    # -- fill / lookup -------------------------------------------------------

    def fill(self, key: tuple, gen0: int, host: np.ndarray,
             tail: np.ndarray | None = None, dev=None,
             device: int | None = None) -> bool:
        """Admit one verified range.  `gen0` is the (owner, bucket)
        generation captured BEFORE the shard reads; a mutation since
        then rejects the fill (the read's bytes may predate the write).
        Returns whether the entry was admitted."""
        owner, bucket = key[0], key[1]
        nbytes = int(host.nbytes) + (int(tail.nbytes) if tail is not None
                                     else 0)
        cap = cache_bytes()
        with self._mu:
            if self._gen.get((owner, bucket), 0) != gen0:
                self.stale_drops += 1
                return False
            if nbytes > cap:
                self.rejects += 1
                return False
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old.nbytes
            self._entries[key] = Entry(key, gen0, host, tail, dev,
                                       device, nbytes)
            self._bytes += nbytes
            self.fills += 1
            while self._bytes > cap and self._entries:
                _, victim = self._entries.popitem(last=False)
                self._bytes -= victim.nbytes
                self.evictions += 1
        return True

    def _valid(self, e: Entry) -> bool:
        return self._gen.get((e.key[0], e.key[1]), 0) == e.gen

    def lookup(self, key: tuple) -> Entry | None:
        with self._mu:
            e = self._entries.get(key)
            if e is None:
                self.misses += 1
                return None
            if not self._valid(e):
                del self._entries[key]
                self._bytes -= e.nbytes
                self.stale_drops += 1
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return e

    def lookup_range(self, owner: int, bucket: str, obj: str,
                     part: int, data_dir: str, algo: str,
                     lo: int, hi: int) -> tuple[Entry, int] | None:
        """Find an entry covering blocks [lo, hi) of the part (heal and
        hedged re-reads probe sub-ranges of what a whole-object GET
        filled).  Returns (entry, block offset of `lo` inside it)."""
        with self._mu:
            for key in list(self._entries):
                if key[:5] != (owner, bucket, obj, part, data_dir) \
                        or key[7] != algo:
                    continue
                e = self._entries[key]
                if not self._valid(e):
                    del self._entries[key]
                    self._bytes -= e.nbytes
                    self.stale_drops += 1
                    continue
                b0, b1 = key[5], key[6]
                if b0 <= lo and hi <= b1:
                    self._entries.move_to_end(key)
                    self.hits += 1
                    return e, lo - b0
            self.misses += 1
            return None

    # -- device residency ----------------------------------------------------

    def device_array(self, e: Entry):
        """The entry's committed jax array, created lazily (and counted
        as ONE crossing) when no verify dispatch pre-placed it.  Returns
        None when jax placement is unavailable."""
        dev = e.dev
        if dev is not None:
            return dev
        from . import devices as devices_mod
        jd = devices_mod.jax_device(e.device if e.device is not None
                                    else 0)
        if jd is None:
            return None
        import jax
        placed = jax.device_put(e.host, jd)
        note_h2d(e.host.nbytes, e.device)
        e.dev = placed
        return placed

    # -- introspection -------------------------------------------------------

    def stats(self) -> dict:
        with self._mu:
            total = self.hits + self.misses
            return {
                "hits": self.hits,
                "misses": self.misses,
                "hit_ratio": (self.hits / total) if total else 0.0,
                "fills": self.fills,
                "evictions": self.evictions,
                "invalidations": self.invalidations,
                "stale_drops": self.stale_drops,
                "rejects": self.rejects,
                "entries": len(self._entries),
                "resident_bytes": self._bytes,
                "capacity_bytes": cache_bytes(),
            }

    def clear(self) -> None:
        with self._mu:
            self._entries.clear()
            self._bytes = 0


# -- process singleton -------------------------------------------------------

_CACHE: DeviceShardCache | None = None
_CACHE_MU = threading.Lock()


def get() -> DeviceShardCache:
    global _CACHE
    c = _CACHE
    if c is None:
        with _CACHE_MU:
            if _CACHE is None:
                _CACHE = DeviceShardCache()
            c = _CACHE
    return c


def stats() -> dict | None:
    """Scrape-side stats: None when no cache was ever created."""
    with _CACHE_MU:
        return None if _CACHE is None else _CACHE.stats()


def reset() -> None:
    """Tests: drop the singleton (fresh generations, zero counters)."""
    global _CACHE
    with _CACHE_MU:
        _CACHE = None
    reset_h2d()


def _reset_after_fork() -> None:
    # A forked child inherits the parent's cache object but its device
    # arrays belong to the parent's jax runtime — drop everything; the
    # child refills from its own verified reads.
    global _CACHE
    _CACHE = None
    reset_h2d()


os.register_at_fork(after_in_child=_reset_after_fork)
