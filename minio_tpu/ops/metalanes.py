"""Per-drive metadata write lanes: group-commit xl.meta publishes.

The shard plane batches (ops/coalesce.py), but until PR 19 the METADATA
plane did not: a 4 KiB inline PUT paid one fsynced ``write_metadata``
per drive through a per-request fan-out.  This module applies the
DispatchLane discipline to that traffic (ROADMAP open item 2; the
reference's format-v2 small-object war, cmd/xl-storage-format-v2.go:25).
It serves writes only: a lane amortises an fsync, and a read has none.
The xl.meta READ fan-out had lanes too until PR 30; on the chip a wait
on one cost 5.9 ms for 0.05 ms of page-cache read, so a request now
reads its own xl.meta (erasure_set._read_metadata).

- one ``MetaLane`` per drive owns a FIFO queue and a lazy daemon
  dispatcher.  It drains concurrent ``_put_inline`` publishes landing
  on the same drive into ONE ``drive.write_metadata_many`` call — every
  xl.meta blob in the batch shares a single journal fsync before any
  caller is acked (group commit; durability ordering unchanged: ack
  strictly after fsync).
- the same adaptive-window EMA + inline-degradation discipline as the
  shard coalescer: an idle lane executes the item on the caller's
  thread through the EXACT single-op drive path (``write_metadata``),
  so a lone request keeps oracle latency and oracle bytes; packing
  only engages once the engine's in-flight counter (or a busy lane)
  proves concurrency.
- fault containment: a failed batch retries its members solo, so one
  poisoned item cannot fail or block an unrelated acked caller; a dead
  dispatcher fails queued handles and degrades every later submit to
  inline single-op dispatch.

Env (read per call so tests flip them without re-importing):

- MTPU_METABATCH=0 disables the plane — the byte-identical oracle
  (single-op fan-out, one fsync per xl.meta publish);
- MTPU_METABATCH_WINDOW_US: max time the oldest queued item waits for
  company once the window engages (default 250);
- MTPU_METABATCH_DEPTH: max items per batched drive call (default 64);
- MTPU_METABATCH_SOLO=1 forces even a lone PUT through the journaled
  batch path (batch of one) — the kill-9 matrix uses this to land the
  ``meta.{stage,fsync,publish}`` crash points deterministically.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque

from ..observe import span as ospan
from ..observe.metrics import DATA_PATH


def enabled() -> bool:
    return os.environ.get("MTPU_METABATCH", "1") != "0"


def solo_forced() -> bool:
    return os.environ.get("MTPU_METABATCH_SOLO", "") == "1"


def window_s() -> float:
    try:
        us = float(os.environ.get("MTPU_METABATCH_WINDOW_US", "250"))
    except ValueError:
        us = 250.0
    return max(0.0, us) / 1e6


def depth() -> int:
    try:
        return max(1, int(os.environ.get("MTPU_METABATCH_DEPTH", "64")))
    except ValueError:
        return 64


class MetaHandle:
    """Future for one submitted metadata op."""

    __slots__ = ("_ev", "_res", "_exc", "_t_enq", "_t_disp")

    def __init__(self):
        self._ev = threading.Event()
        self._res = None
        self._exc: BaseException | None = None
        self._t_enq = time.monotonic()
        self._t_disp: float | None = None

    def result(self, timeout: float | None = 120.0):
        with ospan.span("metalane.wait") as sp:
            if not self._ev.wait(timeout):
                raise TimeoutError("batched metadata op did not complete")
            if self._t_disp is not None:
                sp.tag(queue_ms=round(
                    max(0.0, self._t_disp - self._t_enq) * 1e3, 4))
                self._t_disp = None
        if self._exc is not None:
            raise self._exc
        return self._res

    def _resolve(self, t_disp: float, res=None,
                 exc: BaseException | None = None) -> None:
        self._t_disp = t_disp
        self._res = res
        self._exc = exc
        self._ev.set()


class MetaLane:
    """One drive's scheduler for its xl.meta publishes.

    `solo_fn(item)` is the exact oracle single-op path; `batch_fn`
    (feature-detected `write_metadata_many`, or None for drives
    without one) takes a list of items and returns one `(result, exc)`
    pair per item.  Without a batch op the lane still packs items into
    one dispatcher round of solo calls — no fsync amortization.
    """

    #: queued-item cap as a multiple of the batch depth — beyond this,
    #: submit() blocks (backpressure) instead of buffering unboundedly.
    QUEUE_FACTOR = 4

    def __init__(self, name: str, solo_fn, batch_fn=None):
        self.name = name
        self._solo = solo_fn
        self._batch = batch_fn
        self._mu = threading.Lock()
        self._work = threading.Condition(self._mu)
        self._space = threading.Condition(self._mu)
        self._queue: deque = deque()
        self._dispatching = False
        self._inline = 0
        # Occupancy EMA, same policy as DispatchLane: ~1.0 means lone
        # requests (inline immediately), >1 means packing pays.
        self._ema = 1.0
        self._thread: threading.Thread | None = None
        self._stopped = False
        self._broken: BaseException | None = None
        # Lifetime stats (mirrored into DATA_PATH per dispatch).
        self.dispatches = 0
        self.items = 0
        self.max_items = 0
        self.inline_ops = 0
        self.batch_faults = 0
        self.member_retries = 0

    def busy(self) -> bool:
        return (len(self._queue) > 0 or self._dispatching
                or self._inline > 0 or self._ema > 1.05)

    # -- submission ----------------------------------------------------------

    def submit(self, item) -> MetaHandle:
        h = MetaHandle()
        cap = self.QUEUE_FACTOR * depth()
        with self._mu:
            if self._stopped:
                raise RuntimeError("metadata lane closed")
            # Idle fast path: nothing queued, nothing dispatching, no
            # recent packing — run the ORACLE single-op path on this
            # thread (zero handoff latency, oracle durability
            # mechanics).  MTPU_METABATCH_SOLO disables it so the
            # crash matrix exercises the journal on a batch of one.
            inline = (self._broken is not None
                      or (not solo_forced() and not self._queue
                          and not self._dispatching
                          and self._inline == 0 and self._ema <= 1.05))
            if inline:
                self._inline += 1
            else:
                if self._thread is None:
                    self._thread = threading.Thread(
                        target=self._loop,
                        name=f"mtpu-metalane-{self.name}", daemon=True)
                    self._thread.start()
                while len(self._queue) >= cap:
                    self._space.wait(0.05)
                    cap = self.QUEUE_FACTOR * depth()
                self._queue.append((item, h))
                self._work.notify()
        if inline:
            t0 = time.monotonic()
            try:
                res = self._solo(item)
            except BaseException as e:  # noqa: BLE001 — caller raises
                h._resolve(t0, exc=e)
            else:
                h._resolve(t0, res=res)
            with self._mu:
                self._inline -= 1
                self.inline_ops += 1
            DATA_PATH.record_meta_inline_op()
        return h

    # -- dispatcher ----------------------------------------------------------

    def _loop(self) -> None:
        try:
            while True:
                with self._mu:
                    while not self._queue:
                        if self._stopped:
                            return
                        self._work.wait()
                    budget = depth()
                    # Adaptive window: only hold the head item for
                    # company when recent dispatches actually packed;
                    # always bounded by the oldest item's age.
                    if self._ema > 1.05 and len(self._queue) < budget:
                        deadline = self._queue[0][1]._t_enq + window_s()
                        while (len(self._queue) < budget
                               and not self._stopped):
                            left = deadline - time.monotonic()
                            if left <= 0:
                                break
                            self._work.wait(left)
                    items = []
                    while self._queue and len(items) < budget:
                        items.append(self._queue.popleft())
                    self._dispatching = True
                    self._space.notify_all()
                self._dispatch(items)
                with self._mu:
                    self._dispatching = False
        except BaseException as e:  # noqa: BLE001 — scheduler death
            self._abort(e)

    def _abort(self, exc: BaseException) -> None:
        """Dispatcher death: error every queued handle, route all
        future submits inline (degraded to single-op dispatch — no
        submitter can hang on a scheduler that no longer exists)."""
        with self._mu:
            self._broken = exc
            victims = [h for _, h in self._queue]
            self._queue.clear()
            self._dispatching = False
            self._space.notify_all()
            self._work.notify_all()
        err = RuntimeError(f"metadata lane dispatcher died: {exc!r}")
        t = time.monotonic()
        for h in victims:
            h._resolve(t, exc=err)

    def _dispatch(self, items: list) -> None:
        t_disp = time.monotonic()
        wait_sum = sum(t_disp - h._t_enq for _, h in items)
        try:
            if self._batch is not None:
                results = self._batch([it for it, _ in items])
            else:
                results = []
                for it, _ in items:
                    try:
                        results.append((self._solo(it), None))
                    except Exception as e:  # noqa: BLE001 — per item
                        results.append((None, e))
            if len(results) != len(items):
                raise RuntimeError(
                    f"batch returned {len(results)} results for "
                    f"{len(items)} items")
        except BaseException as e:  # noqa: BLE001 — contain the fault
            with self._mu:
                self.batch_faults += 1
            if len(items) == 1:
                items[0][1]._resolve(t_disp, exc=e)
                return
            # Fault containment: a packed batch carries items from
            # UNRELATED requests — one poisoned member must not fail
            # its neighbors.  Retry each item solo; only the member(s)
            # that still fail get the exception.
            for it, h in items:
                try:
                    res = self._solo(it)
                except BaseException as me:  # noqa: BLE001 — guilty one
                    h._resolve(t_disp, exc=me)
                else:
                    h._resolve(t_disp, res=res)
                with self._mu:
                    self.member_retries += 1
            return
        for (_, h), (res, exc) in zip(items, results):
            h._resolve(t_disp, res=res, exc=exc)
        with self._mu:
            self.dispatches += 1
            self.items += len(items)
            self.max_items = max(self.max_items, len(items))
            self._ema = 0.75 * self._ema + 0.25 * len(items)
        DATA_PATH.record_meta_lane_dispatch(len(items), wait_sum)

    # -- lifecycle -----------------------------------------------------------

    def close(self) -> None:
        with self._mu:
            self._stopped = True
            victims = [h for _, h in self._queue]
            self._queue.clear()
            self._work.notify_all()
            self._space.notify_all()
        t = time.monotonic()
        for h in victims:
            h._resolve(t, exc=RuntimeError("metadata lane closed"))

    def stats(self) -> dict:
        with self._mu:
            return {
                "dispatches": self.dispatches,
                "items": self.items,
                "max_items": self.max_items,
                "inline_ops": self.inline_ops,
                "batch_faults": self.batch_faults,
                "member_retries": self.member_retries,
                "occupancy": (self.items / self.dispatches
                              if self.dispatches else 0.0),
                "pending": len(self._queue),
                "broken": self._broken is not None,
            }


class MetaBatcher:
    """Facade owning one write lane per drive, plus the request-level
    concurrency counter that ignites packing (queue depth alone cannot
    prove concurrency when every idle submit runs inline)."""

    def __init__(self):
        self._mu = threading.Lock()
        # id(drive) -> (drive ref, lane).  The drive ref keeps the id
        # stable for the lane's lifetime.
        self._lanes: dict[int, tuple] = {}
        self._closed = False
        self._inflight_puts = 0

    # -- lane plumbing -------------------------------------------------------

    def write_lane(self, drive) -> MetaLane:
        key = id(drive)
        got = self._lanes.get(key)
        if got is not None:
            return got[1]

        def solo(item):
            vol, obj, fi = item
            drive.write_metadata(vol, obj, fi)

        wmm = getattr(drive, "write_metadata_many", None)

        def batch(items):
            return [(None, e) for e in wmm(items)]

        with self._mu:
            got = self._lanes.get(key)
            if got is None:
                name = f"{getattr(drive, 'endpoint', '?')}-write"
                lane = MetaLane(os.path.basename(str(name)) or name,
                                solo, batch if wmm is not None else None)
                if self._closed:
                    lane._stopped = True
                got = self._lanes[key] = (drive, lane)
        return got[1]

    # -- submission ----------------------------------------------------------

    def submit_write(self, drive, vol: str, obj: str, fi) -> MetaHandle:
        return self.write_lane(drive).submit((vol, obj, fi))

    # -- ignition signal -----------------------------------------------------

    def note_put(self, delta: int) -> None:
        with self._mu:
            self._inflight_puts += delta

    def put_hot(self) -> bool:
        """Whether routing a small-PUT publish fan-out through the
        write lanes is likely to group-commit (vs. taxing a lone
        request with a scheduler handoff)."""
        return (self._inflight_puts > 1
                or any(lane.busy()
                       for _, lane in list(self._lanes.values())))

    # -- lifecycle / introspection ------------------------------------------

    def close(self) -> None:
        with self._mu:
            self._closed = True
            lanes = [lane for _, lane in self._lanes.values()]
        for lane in lanes:
            lane.close()

    def stats(self) -> dict:
        out = {"dispatches": 0, "items": 0, "inline_ops": 0,
               "batch_faults": 0, "member_retries": 0, "max_items": 0,
               "lanes": 0}
        for _, lane in list(self._lanes.values()):
            st = lane.stats()
            out["lanes"] += 1
            for k in ("dispatches", "items", "inline_ops",
                      "batch_faults", "member_retries"):
                out[k] += st[k]
            out["max_items"] = max(out["max_items"], st["max_items"])
        out["occupancy"] = (out["items"] / out["dispatches"]
                            if out["dispatches"] else 0.0)
        return out


# -- process singleton -------------------------------------------------------

_MB: MetaBatcher | None = None
_MB_MU = threading.Lock()


def get() -> MetaBatcher:
    global _MB
    mb = _MB
    if mb is None:
        with _MB_MU:
            if _MB is None:
                _MB = MetaBatcher()
            mb = _MB
    return mb


def reset() -> None:
    """Tests: retire the singleton (its daemon threads exit) so flag
    changes start from cold lanes."""
    global _MB
    with _MB_MU:
        if _MB is not None:
            _MB.close()
        _MB = None


def _reset_after_fork() -> None:
    # A forked child inherits the parent's singleton OBJECT but not its
    # dispatcher threads — submits would queue forever.
    global _MB
    _MB = None


os.register_at_fork(after_in_child=_reset_after_fork)
