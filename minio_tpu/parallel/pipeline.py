"""Reconstruct-pipeline primitives shared by heal and degraded reads.

The PUT path already hides device dispatch behind host framing with a
one-deep `pending` buffer (erasure_set._encode_chunks), and the healthy
GET path prefetches one segment ahead (get_object_iter). This module
gives the *reconstruct* paths — `engine/heal._heal_data` and the
degraded branch of `ErasureSet._read_part` — the same shape as reusable
primitives instead of three hand-rolled variants:

- ``prefetch_map``: ordered map with a bounded read-ahead window — the
  parallelReader analogue (cmd/erasure-decode.go:101): batch *i+1*'s
  drive reads run while batch *i* is being verified/decoded.
- ``StagePipeline``: read → compute → write with exactly one write in
  flight — the in-flight parallelWriter analogue
  (cmd/erasure-encode.go:36): repaired-shard appends for batch *i−1*
  overlap the decode of batch *i*. Appends to one staging file must
  stay ordered, hence the single outstanding write.
- ``run_window`` + ``Frontier``: bounded-worker ordered walk with a
  contiguous-completion frontier, so `heal_drive` can checkpoint its
  HealingTracker at a resume point no unfinished object precedes
  (cf. healErasureSet's bounded workers, cmd/global-heal.go:166).

Everything degrades to inline execution when no pool is given — the
1-core bench host runs the exact same code minus thread hops.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import FIRST_COMPLETED, Executor, wait

from ..observe import span as ospan


def prefetch_map(fn, items, pool: Executor | None, depth: int = 1):
    """Yield ``fn(item)`` in order with up to `depth` calls in flight
    ahead of the consumer. ``pool=None`` or ``depth<1`` runs inline.

    Pooled calls carry the caller's span context (wrap_ctx): stage
    timings — and the `coalesce.wait` queue-wait a stage records when
    it blocks on a coalesced cross-request dispatch — attach to the
    request that submitted the work, not to an anonymous pool thread."""
    if pool is None or depth < 1:
        for item in items:
            yield fn(item)
        return
    fn = ospan.wrap_ctx(fn)
    pending = []
    it = iter(items)
    try:
        for item in it:
            pending.append(pool.submit(fn, item))
            if len(pending) > depth:
                yield pending.pop(0).result()
        while pending:
            yield pending.pop(0).result()
    finally:
        # A consumer that stops early (or a result() that raised) must
        # not leak running futures into the pool.
        for f in pending:
            f.cancel()
        for f in pending:
            if not f.cancelled():
                try:
                    f.result()
                except Exception:  # noqa: BLE001 — draining
                    pass


class StagePipeline:
    """read → compute → write with one write in flight.

    ``run(reads, compute, write)`` drains `reads` (typically already a
    ``prefetch_map`` generator), calls ``compute`` inline, and submits
    ``write`` to the pool keeping exactly one outstanding — batch *i*'s
    decode overlaps batch *i−1*'s staging-file appends while preserving
    append order. With ``pool=None`` every stage runs inline.

    ``on_batch(read_s, compute_s, write_s)``, when given, is invoked
    once per batch with wall-clock seconds spent pulling the item from
    `reads`, in `compute`, and in `write` — the always-on per-stage
    attribution /metrics surfaces for the multipart PUT pipeline.
    With a pool the write time reported alongside a batch is the
    previous batch's (they overlap by design); only the aggregate sums
    are meaningful.

    ``stages=(name, write_name)``, when given, names the spans a traced
    request gets per batch: `name` around read+compute on the caller's
    thread, `write_name` around the write where it runs (the pool
    thread), so what each stage calls nests under it."""

    def __init__(self, pool: Executor | None):
        self.pool = pool

    def run(self, reads, compute, write, on_batch=None,
            stages: tuple[str, str] | None = None) -> int:
        n = 0
        clock = time.perf_counter
        it = iter(reads)
        front, back = stages or (None, None)

        def pull():
            """One batch through read + compute: (result, read_s,
            compute_s), or None when `reads` is exhausted."""
            with (ospan.span(front) if front else ospan.NOOP) as sp:
                t0 = clock()
                try:
                    item = next(it)
                except StopIteration:
                    sp.discard()
                    return None
                t1 = clock()
                res = compute(item)
                return res, t1 - t0, clock() - t1

        def timed_write(res):
            with ospan.span(back) if back else ospan.NOOP:
                t0 = clock()
                write(res)
                return clock() - t0

        if self.pool is None:
            while (got := pull()) is not None:
                res, read_s, compute_s = got
                write_s = timed_write(res)
                if on_batch is not None:
                    on_batch(read_s, compute_s, write_s)
                n += 1
            return n
        wfut = None
        pend_rs = pend_cs = 0.0
        pooled_write = ospan.wrap_ctx(timed_write)

        try:
            while (got := pull()) is not None:
                res, read_s, compute_s = got
                if wfut is not None:
                    w_s = wfut.result()
                    wfut = None
                    if on_batch is not None:
                        on_batch(pend_rs, pend_cs, w_s)
                pend_rs, pend_cs = read_s, compute_s
                wfut = self.pool.submit(pooled_write, res)
                n += 1
            if wfut is not None:
                w_s = wfut.result()
                wfut = None
                if on_batch is not None:
                    on_batch(pend_rs, pend_cs, w_s)
        finally:
            # compute/read raised with a write still in flight: the
            # caller is about to clean up staging files — wait for the
            # append to land first.
            if wfut is not None:
                try:
                    wfut.result()
                except Exception:  # noqa: BLE001 — primary error wins
                    pass
        return n


class Frontier:
    """Contiguous-completion tracker for out-of-order workers.

    ``mark(i)`` records completion of item *i*; ``position`` is the
    count of contiguously completed items from 0 — the only safe
    checkpoint under concurrency (an interrupted run may have healed
    items beyond the frontier; re-healing them on resume is a no-op,
    skipping an unfinished one would lose data). Thread-safe."""

    def __init__(self):
        self._done: set[int] = set()
        self._next = 0
        self._mu = threading.Lock()

    def mark(self, i: int) -> int:
        with self._mu:
            self._done.add(i)
            while self._next in self._done:
                self._done.discard(self._next)
                self._next += 1
            return self._next

    @property
    def position(self) -> int:
        with self._mu:
            return self._next


def run_window(fn, items, pool: Executor | None, window: int,
               stop: threading.Event | None = None):
    """Run ``fn(item)`` over ordered `items` with at most `window` in
    flight; yield ``(idx, item, result, err)`` as each completes
    (completion order, not submission order).

    Bounded by construction: `items` may be a lazy iterator of any
    length — at most `window` tasks exist at once, so neither the pool
    queue nor the materialized work-list grows unboundedly. Setting
    `stop` halts new submissions; in-flight tasks drain. With
    ``pool=None`` or ``window<=1`` items run inline (and `stop` is
    checked between items)."""
    if pool is None or window <= 1:
        for idx, item in enumerate(items):
            if stop is not None and stop.is_set():
                return
            try:
                yield idx, item, fn(item), None
            except Exception as e:  # noqa: BLE001 — caller classifies
                yield idx, item, None, e
        return

    it = enumerate(items)
    futs = {}
    pooled_fn = ospan.wrap_ctx(fn)

    def submit_next() -> bool:
        if stop is not None and stop.is_set():
            return False
        try:
            idx, item = next(it)
        except StopIteration:
            return False
        futs[pool.submit(pooled_fn, item)] = (idx, item)
        return True

    for _ in range(window):
        if not submit_next():
            break
    while futs:
        done, _ = wait(list(futs), return_when=FIRST_COMPLETED)
        for f in done:
            idx, item = futs.pop(f)
            err = f.exception()
            yield idx, item, (None if err is not None else f.result()), err
        while len(futs) < window:
            if not submit_next():
                break
