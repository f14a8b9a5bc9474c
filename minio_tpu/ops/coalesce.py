"""Cross-request dispatch coalescing for the erasure/bitrot data plane.

PRs 1-3 made each *individual* request's kernel traffic batched, but
every dispatch still belongs to exactly one request: N concurrent 1 MiB
PUTs cost N small `encode_and_hash` launches instead of one large one,
and dispatch overhead dominates exactly where the accelerator should
shine.  This module applies the insight behind continuous batching in
inference serving (Orca-style iteration-level scheduling) to object
storage: a dispatcher thread drains per-kernel queues that all
in-flight requests submit to, packs compatible work items into ONE
batched kernel call, and scatters the per-item slices back through
futures.

Since PR 10 the scheduler is sharded per device: `DispatchCoalescer`
is a facade over one `DispatchLane` per visible device (lane count =
`ops/devices.n_devices()`), and every submit carries the device index
its erasure set is affine to (`set_index % n_devices` — the sipHashMod
placement scheme one layer down).  Each lane owns one device, runs its
own dispatcher thread, packs cross-set batches that map to ITS device,
and keeps its own stats block — per-lane occupancy EMAs never pollute
another lane's adaptive-window decision, and concurrent PUTs against
sets on different devices launch kernels concurrently instead of
serializing behind one queue.  The default single-lane configuration
(CPU hosts, MTPU_DEVICES=1) is byte-for-byte the pre-sharding
scheduler.

Scheduling contract (per lane):

- items are compatible when they share a key `(kind, k, m, algo,
  shard_size, ...)` — same kernel, same geometry, so their block axes
  simply concatenate;
- the dispatcher always serves the key whose HEAD item is oldest
  (FIFO across requests — no request is starved because another key is
  busier), and never skips a head item because it is large: an item
  bigger than the batch budget dispatches alone;
- adaptive window: when recent traffic shows no concurrency
  (occupancy EMA ~1) a lone item fires immediately — a single-client
  request never waits.  Under load the dispatcher holds the head item
  up to MTPU_COALESCE_WINDOW_US for company, and the serialization of
  dispatches itself does most of the packing: arrivals during an
  in-flight kernel call land in the next batch for free;
- bounded-queue backpressure: submit() blocks while the total queued
  weight exceeds a small multiple of the batch budget, so a flood of
  writers cannot buffer unbounded shard batches in memory;
- the shape of a device dispatch follows the rows it holds: a batch is
  zero-padded to the smallest built step of its kernel's shape ladder
  (`step_rows` below), not to a fixed 32 blocks, so staging copy,
  upload, program and the fetch of the result shrink with it.

Env (read per call so tests flip them without re-importing):

- MTPU_COALESCE=0 disables coalescing — the direct-dispatch oracle the
  equivalence tests diff against;
- MTPU_COALESCE_WINDOW_US: max time the oldest queued item waits for
  company once the window engages (default 250);
- MTPU_COALESCE_MAX_BATCH: batch budget in 1 MiB-block weight units
  (default 64 — two full per-request encode batches per dispatch);
- MTPU_DEVICES: lane count (see ops/devices.py).
"""

from __future__ import annotations

import functools
import os
import sys
import threading
import time
from collections import deque

import numpy as np

from ..observe import span as ospan
from ..observe.metrics import DATA_PATH
from . import devcache


def enabled() -> bool:
    return os.environ.get("MTPU_COALESCE", "1") != "0"


def window_s() -> float:
    try:
        us = float(os.environ.get("MTPU_COALESCE_WINDOW_US", "250"))
    except ValueError:
        us = 250.0
    return max(0.0, us) / 1e6


def max_batch() -> int:
    try:
        return max(1, int(os.environ.get("MTPU_COALESCE_MAX_BATCH", "64")))
    except ValueError:
        return 64


def pad_batch(x: np.ndarray, multiple: int) -> tuple[np.ndarray, int]:
    """Zero-pad axis 0 up to the next multiple.  Returns (padded,
    original_n).  The dispatch path pads to a step of the shape ladder
    instead (`step_rows`)."""
    n = x.shape[0]
    return _pad_rows(x, n + (-n) % multiple), n


def _pad_rows(x: np.ndarray, rows: int) -> np.ndarray:
    pad = rows - x.shape[0]
    if not pad:
        return x
    return np.concatenate(
        [x, np.zeros((pad,) + x.shape[1:], dtype=x.dtype)])


# -- the shape ladder ---------------------------------------------------------
#
# A device program is compiled per input shape, so a batch is zero-
# padded to one of a bounded set of row counts.  A kernel that declares
# `pad_rows = P` and names its `program` gets the ladder P/32 x LADDER,
# and multiples of P above it: a batch of n rows runs at the smallest
# step that holds it, so the staging copy, the upload, the program and
# the fetch of the result all carry at most 2n rows, not P.  A step
# serves only once its program is built (`build_ladder`, off the serving
# threads, top step first), so the lane never compiles one: until a step
# is ready the next larger one serves, and the top step is the shape
# every batch had before there was a ladder.  Where even the top step
# is not built (a geometry nobody announced, a program met for the first
# time) the submitter builds it on its own thread before it queues the
# item (`DispatchLane.submit`): that one request waits for the compile,
# the lane and everyone on it do not.  A kernel that names no program
# keeps the single shape P.

LADDER = (1, 2, 4, 8, 16, 32)


def step_rows(n: int, pad_rows: int, built=None) -> int:
    """Rows a batch of `n` is padded to under `pad_rows`: the smallest
    ladder step >= n that `built(rows)` admits, else the next multiple
    of `pad_rows`.  THE padding rule of every dispatch kernel."""
    if built is not None and n <= pad_rows and pad_rows % LADDER[-1] == 0:
        unit = pad_rows // LADDER[-1]
        for f in LADDER[:-1]:
            if unit * f >= n and built(unit * f):
                return unit * f
    return n + (-n) % pad_rows


def kernel_rows(fn, n: int, row_shape: tuple) -> int:
    """`step_rows` for kernel `fn` and a batch of `n` rows of
    `row_shape`: what the lanes and the kernels' own bodies pad to."""
    built = None
    if getattr(fn, "ladder", False):
        prog = fn.program()

        def built(rows):
            return prog.built((rows,) + tuple(row_shape), fn.device)
    return step_rows(n, int(getattr(fn, "pad_rows", 1) or 1), built)


_BUILD_MU = threading.Lock()
_BUILD_Q: deque = deque()
_BUILD_THREAD: threading.Thread | None = None


def build_ladder(fn, row_shape: tuple) -> None:
    """Ask for kernel `fn`'s program at every step of its ladder for
    rows of `row_shape`, top step first.  One daemon thread builds what
    is asked, in order, and ends when nothing is left: compiles (or
    loads from the persistent cache) never run on a serving thread.
    Nothing in a process that holds no device (a pool worker)."""
    global _BUILD_THREAD
    from . import devices

    if not getattr(fn, "ladder", False) \
            or devices.jax_device(fn.device) is None:
        return
    unit = fn.pad_rows // LADDER[-1]
    with _BUILD_MU:
        for f in reversed(LADDER):
            _BUILD_Q.append((fn.program(), (unit * f,) + tuple(row_shape),
                             fn.device))
        if _BUILD_THREAD is None:
            _BUILD_THREAD = threading.Thread(
                target=_build_loop, name="mtpu-ladder-build", daemon=True)
            _BUILD_THREAD.start()


def _build_loop() -> None:
    global _BUILD_THREAD
    while True:
        with _BUILD_MU:
            if not _BUILD_Q:
                _BUILD_THREAD = None
                return
            prog, shape, device = _BUILD_Q.popleft()
        try:
            prog.build(shape, device)
        except Exception as e:  # noqa: BLE001 — the next step up serves
            print(f"minio_tpu: ladder step {shape} on lane {device} "
                  f"not built: {e!r}", file=sys.stderr, flush=True)


def ladder_idle() -> bool:
    """Whether every build asked for so far is done."""
    return _BUILD_THREAD is None


def ladder_wait() -> None:
    """Block until every build asked for so far is done (tests)."""
    while (t := _BUILD_THREAD) is not None:
        t.join()


class _BufPool:
    """Free-list of uint8 scratch buffers for kernels whose OUTPUT is
    large (the fused host put_frame writes ~2x the data size of framed
    shards): a fresh mmap-threshold allocation per dispatch pays
    ~0.5 ms/MiB in page faults, so released dispatch buffers are reused
    — the cross-request analogue of ecio_native's per-thread arena,
    which the coalescer cannot use because results outlive the
    dispatcher thread's next call."""

    KEEP = 4

    def __init__(self):
        self._mu = threading.Lock()
        self._bufs: list[np.ndarray] = []

    def rent(self, nbytes: int) -> np.ndarray:
        with self._mu:
            for i, b in enumerate(self._bufs):
                if b.size >= nbytes:
                    return self._bufs.pop(i)
        return np.empty(nbytes, dtype=np.uint8)

    def give(self, buf: np.ndarray) -> None:
        with self._mu:
            self._bufs.append(buf)
            if len(self._bufs) > self.KEEP:
                self._bufs.sort(key=lambda b: b.size)
                self._bufs.pop(0)       # drop the smallest


class DispatchCtx:
    """Per-dispatch context handed to kernels.  `rent()` borrows a
    pooled scratch buffer that is returned to the pool once every item
    of the dispatch has been release()d by its consumer (refcounted —
    an unreleased handle just forfeits reuse, never corrupts)."""

    __slots__ = ("_pool", "_mu", "_refs", "buf")

    def __init__(self, pool: _BufPool, nitems: int):
        self._pool = pool
        self._mu = threading.Lock()
        self._refs = nitems
        self.buf = None

    def rent(self, nbytes: int) -> np.ndarray:
        self.buf = self._pool.rent(nbytes)
        return self.buf

    def _deref(self) -> None:
        with self._mu:
            self._refs -= 1
            done = self._refs == 0
        if done and self.buf is not None:
            self._pool.give(self.buf)
            self.buf = None


class Handle:
    """Future for one submitted work item.  `result()` blocks until the
    dispatcher resolved the item — the caller's block is the
    `coalesce.wait` stage of its span tree, tagged with the queue wait
    (enqueue to dispatch start) and the lane's device; `release()`
    tells the buffer pool the caller is done with any pooled views this
    result aliases.  `rid` is the submitter's request id while it is
    traced: what a lane dispatch lists as its `members`."""

    __slots__ = ("_ev", "_res", "_exc", "_t_enq", "_t_disp", "_ctx",
                 "weight", "nrows", "device", "rid")

    def __init__(self, weight: int, nrows: int, device: int = 0):
        self._ev = threading.Event()
        self._res = None
        self._exc: BaseException | None = None
        self._t_enq = time.monotonic()
        self._t_disp: float | None = None
        self._ctx: DispatchCtx | None = None
        self.weight = weight
        self.nrows = nrows
        self.device = device
        self.rid = ospan.request_id()

    def result(self, timeout: float | None = 120.0):
        with ospan.span("coalesce.wait") as sp:
            if not self._ev.wait(timeout):
                raise TimeoutError("coalesced dispatch did not complete")
            if self._t_disp is not None:
                sp.tag(device=self.device, queue_ms=round(
                    max(0.0, self._t_disp - self._t_enq) * 1e3, 4))
                self._t_disp = None
        if self._exc is not None:
            raise self._exc
        return self._res

    def release(self) -> None:
        ctx, self._ctx = self._ctx, None
        if ctx is not None:
            ctx._deref()


class DispatchLane:
    """One device's scheduler: per-key FIFO queues + one daemon
    dispatcher thread (started lazily on first queued submit).  All
    state — queues, occupancy EMA, buffer pool, lifetime stats — is
    lane-private, so one device's traffic never skews another lane's
    adaptive-window decision."""

    #: queued-weight cap as a multiple of the batch budget — beyond
    #: this, submit() blocks (backpressure) instead of buffering.
    QUEUE_FACTOR = 4

    #: The lane's wall time since it was made, partitioned: `no_work`
    #: (parked with empty queues and nothing in flight), `linger` (the
    #: adaptive window, the in-flight window and the scheduler's own
    #: bookkeeping), then the four phases of a dispatch — `pack`
    #: (concatenate / copy into staging, pad), `h2d` (device_put),
    #: `launch` (the kernel call; a kernel without a launch/resolve
    #: split, a host kernel, runs whole under it), `device_wait` (the
    #: resolve: blocked until the device's result is on the host.  A
    #: device kernel's way back is begun at its launch and the lane
    #: thread resolves a batch one dispatch after it launched it, so
    #: there the state holds what is left of the program and of the
    #: transfer once the next batch is packed, uploaded and launched,
    #: and no copy: results are views of the arrays the runtime filled.
    #: A dispatch run whole, inline or serial, waits here for all of
    #: both).  The lane thread's state wins while it is awake; a
    #: dispatch run inline on a request thread is charged while the
    #: thread is parked.  One set of timers: the `lane.*` spans open at
    #: the same edges, and `pack_s` / `h2d_s` / `resolve_s` in stats()
    #: are these.
    STATES = ("no_work", "linger", "pack", "h2d", "launch",
              "device_wait")

    def __init__(self, device: int = 0):
        self.device = int(device)
        self._clk_mu = threading.Lock()
        self._state = "no_work"
        self._state_t = self.t_created = time.monotonic()
        self._state_s = dict.fromkeys(self.STATES, 0.0)
        self._awake = False
        self._mu = threading.Lock()
        self._work = threading.Condition(self._mu)
        self._space = threading.Condition(self._mu)
        self._queues: dict[tuple, deque] = {}
        self._fns: dict[tuple, object] = {}
        self._pending_weight = 0
        self._pending_items = 0
        self._dispatching = False
        self._inline = 0
        # Occupancy EMA drives the adaptive window: ~1.0 means lone
        # requests (fire immediately), >1 means concurrent traffic is
        # actually packing (waiting the window pays for itself).
        self._ema = 1.0
        self._thread: threading.Thread | None = None
        self._stopped = False
        # Set (to the fatal exception) if the dispatcher thread ever
        # dies: queued handles are failed and every later submit runs
        # inline on the caller — degraded to direct dispatch, but no
        # submitter can hang on a scheduler that no longer exists.
        self._broken: BaseException | None = None
        self._bufs = _BufPool()
        # H2D pipeline state (ISSUE 17: pinned staging + double-buffered
        # uploads).  Two page-aligned bpool staging leases alternate per
        # dispatch; `_pending` holds at most ONE launched-but-unresolved
        # batch: while its kernel executes on-device, the next batch
        # packs into the spare staging buffer and ships via async
        # device_put — host pack/scatter overlapped with device compute.
        # Lane-thread-private except for the stats counters.
        self._staging: list = [None, None]
        self._staging_flip = 0
        self._pending: tuple | None = None
        # Lifetime stats (mirrored into DATA_PATH per dispatch).
        self.dispatches = 0
        self.items = 0
        self.weight = 0
        self.wait_s = 0.0
        self.max_items = 0
        self.batch_faults = 0
        self.member_retries = 0
        self.h2d_bytes = 0
        self.h2d_dispatches = 0
        self.pipeline_dispatches = 0
        self.overlap_s = 0.0

    # -- the lane's clock ----------------------------------------------------

    def _clock(self, state: str, inline: bool = False) -> None:
        """Enter `state`: the time since the last transition goes to
        the state that ends here."""
        now = time.monotonic()
        with self._clk_mu:
            if inline:
                if self._awake:
                    return
            else:
                self._awake = state != "no_work"
            self._state_s[self._state] += now - self._state_t
            self._state, self._state_t = state, now

    def _stage(self, state: str, inline: bool = False):
        """One edge for both clocks: the lane's state and, inside a
        traced dispatch, the `lane.<state>` span."""
        self._clock(state, inline)
        return ospan.span(_LANE_SPAN[state])

    def state_now(self) -> tuple[str, dict[str, float]]:
        """The state the lane is in, and seconds per state up to now;
        they sum to the lane's age."""
        now = time.monotonic()
        with self._clk_mu:
            out = dict(self._state_s)
            out[self._state] += now - self._state_t
            return self._state, out

    def state_seconds(self) -> dict[str, float]:
        return self.state_now()[1]

    def _dispatch_span(self, key: tuple, fn, items: list[tuple],
                       rows: int, padded: int):
        """The `lane.dispatch` span of one batch: nested under the
        request when the dispatch runs inline on its thread, else a
        root of the lane thread's own, naming the requests it serves.
        `program` is the batch's key, or what the kernel says of itself
        (`span_tags`: a decode's one program name and its `targets`)."""
        if not ospan.TRACER.enabled:
            return ospan.NOOP
        tags = getattr(fn, "span_tags", None) \
            or {"program": "/".join(str(p) for p in key)}
        return ospan.span_or_root(
            "lane.dispatch", device=self.device, items=len(items),
            rows=rows, padded_rows=padded,
            members=sorted({h.rid for _, h in items if h.rid}), **tags)

    # -- submission ----------------------------------------------------------

    def submit(self, key: tuple, payload: np.ndarray, fn,
               weight: int | None = None) -> Handle:
        """Queue one work item.  `payload` is the item's batch (axis 0
        is the concat axis); `fn(stacked, spans, ctx)` computes the
        whole coalesced batch and returns one result per (lo, hi) span;
        `weight` is the item's cost in budget units (default: axis-0
        length).  All submitters of a key MUST pass an equivalent fn —
        the key encodes every parameter the kernel closes over."""
        payload = np.asarray(payload)
        nrows = int(payload.shape[0]) if payload.ndim else 1
        h = Handle(int(weight) if weight is not None else nrows, nrows,
                   self.device)
        prog = getattr(fn, "program", None)
        if prog is not None and nrows <= fn.pad_rows:
            # The lane never compiles: the shape every batch of up to
            # pad_rows rows can fall back to is built here, on the
            # caller's thread, if nobody built it yet (once a program).
            prog().build((fn.pad_rows,) + payload.shape[1:], fn.device)
        cap = self.QUEUE_FACTOR * max_batch()
        with self._mu:
            if self._stopped:
                raise RuntimeError("coalescer closed")
            # Idle fast path: nothing queued, nothing in flight, no
            # recent packing — run the dispatch on THIS thread (direct
            # semantics: a lone request pays zero handoff latency, the
            # measured ~25% single-client PUT tax of waking a scheduler
            # thread per batch on a 1-core host).  A concurrent submit
            # observes `_inline` and queues instead, so the moment two
            # requests overlap, packing begins.
            inline = (self._broken is not None
                      or (not self._pending_items and not self._dispatching
                          and self._inline == 0 and self._ema <= 1.05))
            if inline:
                self._inline += 1
            else:
                if self._thread is None:
                    self._thread = threading.Thread(
                        target=self._loop,
                        name=f"mtpu-coalesce-d{self.device}",
                        daemon=True)
                    self._thread.start()
                # Backpressure: an item never waits on its OWN weight
                # (a single oversized item must always be admissible).
                if self._pending_weight and \
                        self._pending_weight + h.weight > cap:
                    with ospan.span("coalesce.backpressure"):
                        while self._pending_weight and \
                                self._pending_weight + h.weight > cap:
                            self._space.wait(0.05)
                            cap = self.QUEUE_FACTOR * max_batch()
                q = self._queues.get(key)
                if q is None:
                    q = self._queues[key] = deque()
                self._fns[key] = fn
                q.append((payload, h))
                self._pending_weight += h.weight
                self._pending_items += 1
                self._work.notify()
        if inline:
            try:
                self._dispatch([(payload, h)], h.weight, fn, key,
                               inline=True)
            finally:
                self._clock("no_work", inline=True)
                with self._mu:
                    self._inline -= 1
        return h

    # -- dispatcher ----------------------------------------------------------

    def _queue_weight(self, q: deque) -> int:
        return sum(h.weight for _, h in q)

    def _pick_key(self):
        oldest_key, oldest_t = None, None
        for key, q in self._queues.items():
            if q and (oldest_t is None or q[0][1]._t_enq < oldest_t):
                oldest_key, oldest_t = key, q[0][1]._t_enq
        return oldest_key

    def _loop(self) -> None:
        self._clock("linger")
        try:
            while True:
                do_drain = False
                with self._mu:
                    key = self._pick_key()
                    while key is None:
                        if self._pending is not None:
                            # A launched batch is in flight.  Give new
                            # work one window to arrive (so its pack
                            # overlaps the executing kernel), then
                            # resolve — NEVER park indefinitely on
                            # `_work` with an unresolved launch: its
                            # waiters would deadlock against an idle
                            # queue.
                            self._work.wait(window_s() or 0.0005)
                            key = self._pick_key()
                            if key is None:
                                do_drain = True
                            break
                        if self._stopped:
                            return
                        self._clock("no_work")
                        self._work.wait()
                        self._clock("linger")
                        key = self._pick_key()
                    if not do_drain:
                        q = self._queues[key]
                        budget = max_batch()
                        # Adaptive window: only wait for company when
                        # the occupancy EMA says concurrent traffic
                        # exists; always bounded by the oldest item's
                        # age.  With a launch in flight the kernel IS
                        # the company — skip the wait and pack now.
                        if (self._pending is None and self._ema > 1.05
                                and self._queue_weight(q) < budget):
                            deadline = q[0][1]._t_enq + window_s()
                            while (self._queue_weight(q) < budget
                                   and not self._stopped):
                                left = deadline - time.monotonic()
                                if left <= 0:
                                    break
                                self._work.wait(left)
                        items: list[tuple] = []
                        w = 0
                        while q and (not items
                                     or w + q[0][1].weight <= budget):
                            payload, h = q.popleft()
                            items.append((payload, h))
                            w += h.weight
                        self._pending_weight -= w
                        self._pending_items -= len(items)
                        fn = self._fns[key]
                        self._dispatching = True
                        self._space.notify_all()
                if do_drain:
                    self._drain_pipeline()
                else:
                    self._dispatch(items, w, fn, key, pipelined=True)
                self._clock("linger")
                with self._mu:
                    # Stay "dispatching" while a launch is unresolved so
                    # the inline fast path cannot race a pending batch.
                    self._dispatching = self._pending is not None
        except BaseException as e:  # noqa: BLE001 — scheduler death
            # _dispatch contains kernel faults itself, so anything
            # escaping here is scheduler logic dying — fail everything
            # queued rather than leaving submitters parked on handles
            # no thread will ever resolve.
            self._abort(e)
        finally:
            self._clock("no_work")

    def _abort(self, exc: BaseException) -> None:
        """Dispatcher death: error every queued handle, route all future
        submits inline (direct-dispatch degradation — correctness and
        liveness over packing)."""
        with self._mu:
            self._broken = exc
            victims: list[Handle] = []
            pending, self._pending = self._pending, None
            if pending is not None:
                victims.extend(h for _, h in pending[1])
                pending[6].resume().tag(error=True).__exit__(
                    None, None, None)
            for q in self._queues.values():
                victims.extend(h for _, h in q)
                q.clear()
            self._queues.clear()
            self._fns.clear()
            self._pending_weight = 0
            self._pending_items = 0
            self._dispatching = False
            self._space.notify_all()
            self._work.notify_all()
        err = RuntimeError(f"coalescer dispatcher died: {exc!r}")
        for h in victims:
            h._exc = err
            h._ev.set()

    def _dispatch(self, items: list[tuple], w: int, fn, key: tuple,
                  pipelined: bool = False, inline: bool = False) -> None:
        if pipelined:
            launch = getattr(fn, "launch", None)
            if launch is not None and devcache.h2d_pipeline_enabled():
                if self._dispatch_pipelined(items, w, fn, key, launch):
                    return
            # Serial dispatch from the lane thread must not outrun a
            # still-pending launch (per-key FIFO): resolve it first.
            if self._pending is not None:
                self._drain_pipeline()
        t_disp = time.monotonic()
        n = sum(h.nrows for _, h in items)
        padded = kernel_rows(fn, n, items[0][0].shape[1:])
        with self._dispatch_span(key, fn, items, n, padded):
            self._dispatch_serial(items, w, fn, t_disp, inline, padded)

    def _dispatch_serial(self, items: list[tuple], w: int, fn,
                         t_disp: float, inline: bool,
                         padded: int) -> None:
        """One batch through its kernel on the calling thread.  A
        kernel with a launch/resolve split runs as the four phases the
        pipelined path has (the same calls its own body makes), padded
        to `padded` rows, its step of the ladder; any other runs whole
        under `launch`."""
        ctx = DispatchCtx(self._bufs, len(items))
        launch = getattr(fn, "launch", None)
        try:
            with self._stage("pack", inline):
                if len(items) == 1:
                    stacked = items[0][0]
                else:
                    stacked = np.concatenate([p for p, _ in items],
                                             axis=0)
                spans = []
                lo = 0
                for _, h in items:
                    spans.append((lo, lo + h.nrows))
                    lo += h.nrows
                n = lo
                if launch is not None:
                    x = _pad_rows(stacked, padded)
            if launch is None:
                with self._stage("launch", inline):
                    results = fn(stacked, spans, ctx)
            else:
                from . import devices as devices_mod

                with self._stage("h2d", inline):
                    x = devices_mod.put(x, self.device)
                with self._stage("launch", inline):
                    resolve = launch(x, n, spans, ctx)
                with self._stage("device_wait", inline):
                    results = resolve()
        except BaseException as e:  # noqa: BLE001 — contain the fault
            if ctx.buf is not None:
                self._bufs.give(ctx.buf)
                ctx.buf = None
            with self._mu:
                self.batch_faults += 1
            if len(items) == 1:
                h = items[0][1]
                h._t_disp = t_disp
                h._exc = e
                h._ev.set()
                DATA_PATH.record_co_fault(0)
                return
            # Fault containment: a packed batch carries spans from
            # UNRELATED requests — one poisoned member must not fail
            # its neighbors.  Retry each span as its own dispatch; only
            # the member(s) that still fail get the exception.
            DATA_PATH.record_co_fault(len(items))
            for payload, h in items:
                mctx = DispatchCtx(self._bufs, 1)
                try:
                    res = fn(payload, [(0, h.nrows)], mctx)[0]
                except BaseException as me:  # noqa: BLE001 — guilty span
                    if mctx.buf is not None:
                        self._bufs.give(mctx.buf)
                        mctx.buf = None
                    h._exc = me
                else:
                    h._ctx = mctx
                    h._res = res
                with self._mu:
                    self.member_retries += 1
                h._t_disp = t_disp
                h._ev.set()
            return
        wait_sum = 0.0
        for (_, h), res in zip(items, results):
            wait_sum += t_disp - h._t_enq
            h._t_disp = t_disp
            h._ctx = ctx
            h._res = res
            h._ev.set()
        with self._mu:
            self.dispatches += 1
            self.items += len(items)
            self.weight += w
            self.wait_s += wait_sum
            self.max_items = max(self.max_items, len(items))
            self._ema = 0.75 * self._ema + 0.25 * len(items)
        DATA_PATH.record_coalesce_dispatch(len(items), w, wait_sum)
        DATA_PATH.record_lane_dispatch(self.device, len(items), w,
                                       wait_sum, n, padded)

    # -- pinned-staging H2D pipeline (ISSUE 17 tentpole) ---------------------

    def _staging_view(self, slot: int, nbytes: int) -> np.ndarray:
        """The slot's page-aligned bpool staging lease, grown on demand.
        A slot is only ever reused two dispatches later, by which point
        the batch that last packed into it has been resolved (resolve
        syncs the kernel), so growth may release the old lease safely."""
        from . import bpool

        lease = self._staging[slot]
        if lease is None or lease.view is None \
                or lease.view.nbytes < nbytes:
            if lease is not None:
                lease.release()
            lease = self._staging[slot] = bpool.default_pool().get(nbytes)
        return lease.view[:nbytes]

    def _dispatch_pipelined(self, items: list[tuple], w: int, fn,
                            key: tuple, launch) -> bool:
        """Pack the batch into the spare staging buffer, ship it with an
        async device_put, launch the kernel, and resolve the PREVIOUS
        launch afterwards — so this batch's host work (pack + upload
        issue) overlaps the previous batch's device execution.  Returns
        False (nothing dispatched) when the batch is not pipeline-
        eligible; the caller falls back to the serial path."""
        from . import devices as devices_mod

        dev = devices_mod.jax_device(self.device)
        first = items[0][0]
        if dev is None or first.dtype != np.uint8 or first.ndim < 2:
            return False
        row_shape = first.shape[1:]
        row_bytes = first.itemsize
        for d in row_shape:
            row_bytes *= int(d)
        if row_bytes <= 0:
            return False
        for p, _ in items:
            if p.dtype != np.uint8 or p.shape[1:] != row_shape:
                return False
        t0 = time.monotonic()
        n = sum(h.nrows for _, h in items)
        padded = kernel_rows(fn, n, row_shape)
        need = padded * row_bytes
        # Open from this pack to this batch's resolve, one dispatch
        # later: suspended in between, while the lane packs the next.
        root = self._dispatch_span(key, fn, items, n, padded).__enter__()
        with self._stage("pack"):
            slot = self._staging_flip
            self._staging_flip ^= 1
            view = self._staging_view(slot, need).reshape(
                (padded,) + row_shape)
            lo = 0
            for p, h in items:
                view[lo:lo + h.nrows] = p
                lo += h.nrows
            if padded > n:
                view[n:] = 0
        t_pack = time.monotonic()
        import jax

        with self._stage("h2d"):
            x = jax.device_put(view, dev)  # async H2D from pinned staging
            devcache.note_h2d(need, self.device)
        spans = []
        lo = 0
        for _, h in items:
            spans.append((lo, lo + h.nrows))
            lo += h.nrows
        ctx = DispatchCtx(self._bufs, len(items))
        try:
            with self._stage("launch"):
                resolve = launch(x, n, spans, ctx)
        except BaseException:  # noqa: BLE001 — fall back to serial
            # Launch is the cheap half (placement + trace); a fault here
            # re-runs the batch on the serial path, whose containment
            # retries members solo.
            if ctx.buf is not None:
                self._bufs.give(ctx.buf)
                ctx.buf = None
            root.tag(error=True).__exit__(None, None, None)
            return False
        root.suspend()
        prev, self._pending = self._pending, (
            resolve, items, w, fn, ctx, t_pack, root, n, padded)
        host_s = time.monotonic() - t0
        with self._mu:
            self.h2d_bytes += need
            self.h2d_dispatches += 1
            self.pipeline_dispatches += 1
            if prev is not None:
                # Everything this batch just did on the host ran while
                # `prev`'s kernel executed on-device.
                self.overlap_s += host_s
        if prev is not None:
            self._resolve(prev)
        return True

    def _drain_pipeline(self) -> None:
        pending, self._pending = self._pending, None
        if pending is not None:
            self._resolve(pending)

    def _resolve(self, pending: tuple) -> None:
        """Sync one launched batch and scatter its results — the second
        phase of `_dispatch`, deferred one dispatch behind the launch."""
        resolve, items, w, fn, ctx, t_disp, root, n, padded = pending
        root.resume()
        try:
            self._resolve_open(resolve, items, w, fn, ctx, t_disp, n,
                               padded)
        finally:
            root.__exit__(None, None, None)

    def _resolve_open(self, resolve, items: list[tuple], w: int, fn, ctx,
                      t_disp: float, n: int, padded: int) -> None:
        try:
            with self._stage("device_wait"):
                results = resolve()
        except BaseException:  # noqa: BLE001 — contain the fault
            if ctx.buf is not None:
                self._bufs.give(ctx.buf)
                ctx.buf = None
            with self._mu:
                self.batch_faults += 1
            # Same containment contract as the serial path: a packed
            # batch carries spans from unrelated requests — retry each
            # member solo; only the guilty span(s) keep the exception.
            DATA_PATH.record_co_fault(len(items))
            for payload, h in items:
                mctx = DispatchCtx(self._bufs, 1)
                try:
                    res = fn(payload, [(0, h.nrows)], mctx)[0]
                except BaseException as me:  # noqa: BLE001
                    if mctx.buf is not None:
                        self._bufs.give(mctx.buf)
                        mctx.buf = None
                    h._exc = me
                else:
                    h._ctx = mctx
                    h._res = res
                with self._mu:
                    self.member_retries += 1
                h._t_disp = t_disp
                h._ev.set()
            return
        wait_sum = 0.0
        for (_, h), res in zip(items, results):
            wait_sum += t_disp - h._t_enq
            h._t_disp = t_disp
            h._ctx = ctx
            h._res = res
            h._ev.set()
        with self._mu:
            self.dispatches += 1
            self.items += len(items)
            self.weight += w
            self.wait_s += wait_sum
            self.max_items = max(self.max_items, len(items))
            self._ema = 0.75 * self._ema + 0.25 * len(items)
        DATA_PATH.record_coalesce_dispatch(len(items), w, wait_sum)
        DATA_PATH.record_lane_dispatch(self.device, len(items), w,
                                       wait_sum, n, padded)

    # -- lifecycle / introspection ------------------------------------------

    def close(self) -> None:
        with self._mu:
            self._stopped = True
            # Anything still queued will never be served — fail it now
            # (a retiring scheduler must not leave submitters waiting
            # out their result() timeout).
            victims: list[Handle] = []
            for q in self._queues.values():
                victims.extend(h for _, h in q)
                q.clear()
            self._queues.clear()
            self._fns.clear()
            self._pending_weight = 0
            self._pending_items = 0
            self._work.notify_all()
            self._space.notify_all()
        for h in victims:
            h._exc = RuntimeError("coalescer closed")
            h._ev.set()

    def stats(self) -> dict:
        state_s = self.state_seconds()
        with self._mu:
            return {
                "device": self.device,
                "dispatches": self.dispatches,
                "items": self.items,
                "weight": self.weight,
                "wait_s": self.wait_s,
                "max_items": self.max_items,
                "occupancy": (self.items / self.dispatches
                              if self.dispatches else 0.0),
                "pending_items": self._pending_items,
                "pending_weight": self._pending_weight,
                "batch_faults": self.batch_faults,
                "member_retries": self.member_retries,
                "h2d_bytes": self.h2d_bytes,
                "h2d_dispatches": self.h2d_dispatches,
                "pipeline_dispatches": self.pipeline_dispatches,
                "pack_s": state_s["pack"],
                "h2d_s": state_s["h2d"],
                "resolve_s": state_s["device_wait"],
                "overlap_s": self.overlap_s,
                "state_s": state_s,
                "broken": self._broken is not None,
            }


class DispatchCoalescer:
    """Per-device lane facade: routes each submit to the lane owning
    the target device (`device % n_lanes`, so a lane index is always
    valid even when the topology shrank) and aggregates lane stats.
    Lane count is resolved lazily from `ops/devices.n_devices()` on
    first use and then frozen for the instance — tests flip
    MTPU_DEVICES and call `coalesce.reset()` for a fresh topology.

    With one lane (the host/oracle default) the facade is a thin
    pass-through around the exact pre-sharding scheduler."""

    def __init__(self, nlanes: int | None = None):
        self._lanes_mu = threading.Lock()
        self._want_lanes = nlanes
        self._lanes: dict[int, DispatchLane] = {}
        self._closed = False

    def nlanes(self) -> int:
        n = self._want_lanes
        if n is None:
            from . import devices

            n = self._want_lanes = devices.n_devices()
        return n

    def lane(self, device: int = 0) -> DispatchLane:
        d = int(device) % self.nlanes()
        lane = self._lanes.get(d)
        if lane is None:
            with self._lanes_mu:
                lane = self._lanes.get(d)
                if lane is None:
                    lane = DispatchLane(device=d)
                    if self._closed:
                        # Post-close stragglers (a request still in
                        # flight when the coalescer closed) get a lane
                        # that refuses submits but never hangs.
                        lane._stopped = True
                    self._lanes[d] = lane
        return lane

    # -- pass-throughs keyed by device --------------------------------------

    def submit(self, key: tuple, payload: np.ndarray, fn,
               weight: int | None = None, device: int = 0) -> Handle:
        return self.lane(device).submit(key, payload, fn, weight)

    # -- single-lane compatibility surface ----------------------------------
    # The scheduler unit tests (and the idle fast-path contract) poke
    # lane internals through the facade; with lanes these map to lane 0.

    @property
    def _ema(self) -> float:
        return self.lane(0)._ema

    @_ema.setter
    def _ema(self, v: float) -> None:
        self.lane(0)._ema = v

    @property
    def _thread(self):
        ln = self._lanes.get(0)
        return None if ln is None else ln._thread

    # -- lifecycle / introspection ------------------------------------------

    def close(self) -> None:
        with self._lanes_mu:
            self._closed = True
            lanes = list(self._lanes.values())
        for ln in lanes:
            ln.close()

    def lane_stats(self) -> dict[int, dict]:
        """Per-lane stats for lanes that have actually been touched."""
        return {d: ln.stats() for d, ln in sorted(self._lanes.items())}

    def stats(self) -> dict:
        per = self.lane_stats()
        out = {
            "dispatches": 0, "items": 0, "weight": 0, "wait_s": 0.0,
            "max_items": 0, "pending_items": 0, "pending_weight": 0,
            "batch_faults": 0, "member_retries": 0,
            "h2d_bytes": 0, "h2d_dispatches": 0,
            "pipeline_dispatches": 0, "pack_s": 0.0, "h2d_s": 0.0,
            "resolve_s": 0.0, "overlap_s": 0.0,
        }
        broken = False
        for st in per.values():
            for k in ("dispatches", "items", "weight", "wait_s",
                      "pending_items", "pending_weight", "batch_faults",
                      "member_retries", "h2d_bytes", "h2d_dispatches",
                      "pipeline_dispatches", "pack_s", "h2d_s",
                      "resolve_s", "overlap_s"):
                out[k] += st[k]
            out["max_items"] = max(out["max_items"], st["max_items"])
            broken = broken or st["broken"]
        out["occupancy"] = (out["items"] / out["dispatches"]
                            if out["dispatches"] else 0.0)
        out["broken"] = broken
        out["n_lanes"] = self.nlanes()
        out["lanes"] = per
        return out


# -- shared kernels ----------------------------------------------------------
#
# The device kernels of the data plane, built from the parameters a
# coalescer key carries: the engine (engine/erasure_set.py) and the
# pool's device owner (ops/ipc_dispatch.kernel_from_key) build the same
# kernel from the same key.

def _device_kernel(start, pad_rows: int, device: int | None,
                   program=None):
    """The dispatch kernel around `start(x, spans) -> (outputs,
    scatter)`: `start` runs the program on the placed batch and names
    the device arrays that have to come back; `scatter(*host)` slices
    those arrays, once on the host, into one result per span.  The way
    back is kept here, one rule for every device kernel:
    `launch` asks the runtime for the outputs at once
    (`devcache.start_fetch`), so their way back runs on the runtime's
    threads while the lane packs and uploads the next batch, and the
    `resolve` it returns waits for the program (`devcache.wait_ready`),
    fetches them (`devcache.fetch`: the arrays the runtime filled),
    scatters, and counts what a scatter copied
    (`devcache.note_result_copies`: views cost nothing): under the
    lane's `device_wait`, `lane.program_wait`, `lane.fetch` and
    `lane.scatter`.

    The lanes drive the pair themselves (pack, upload, launch, then
    resolve one dispatch later); called whole (a solo retry, a direct
    call) the kernel pads to its step, uploads, and resolves at once.
    `program()` gives the ops/fused.Program the launch runs, where it
    is one: its `pad_rows` shape is then built before a lane sees a
    batch, and the batch runs at the smallest built step of the shape
    ladder (`ladder`); a kernel that names no program runs at a
    multiple of `pad_rows`.  The program is asked for only where a
    batch is dispatched: a pool worker builds kernels (their keys
    travel to the owner) and must not reach for JAX."""
    from . import devices

    def launch(x, n, spans, ctx):
        outputs, scatter = start(x, spans)
        devcache.start_fetch(outputs)

        def resolve():
            # The resolve in its parts, spans inside a traced dispatch:
            # what is left of the program, what is left of the transfer
            # begun above, and the host's own slicing.
            with ospan.span("lane.program_wait"):
                devcache.wait_ready(outputs)
            with ospan.span("lane.fetch"):
                host = [devcache.fetch(o) for o in outputs]
            with ospan.span("lane.scatter"):
                results = scatter(*host)
                devcache.note_result_copies(host, results)
            return results

        return resolve

    def kernel(stacked, spans, ctx):
        n = stacked.shape[0]
        x = _pad_rows(stacked, kernel_rows(kernel, n, stacked.shape[1:]))
        return kernel.launch(devices.put(x, device), n, spans, ctx)()

    kernel.launch = launch
    kernel.pad_rows = pad_rows
    kernel.device = device
    kernel.program = program
    kernel.ladder = program is not None
    return kernel


def make_encode_kernel(k: int, m: int, algo: str | None, pad_rows: int,
                       device: int | None = None, codec=None):
    """Encode over stacked (B, K, S) blocks -> (parity, digests) per
    span, the pair the direct dispatch produces.  With no `codec` it is
    the device program of ops/fused.py, sized by the ladder of
    `pad_rows`: parity AND bitrot digests in one launch, or with `algo`
    None (an algorithm the host hashes) the parity alone, digests None.
    With a host `codec`, parity only, unpadded."""
    if codec is not None:
        def kernel(stacked, spans, ctx):
            parity = np.asarray(codec.encode_blocks(stacked))
            return [(parity[lo:hi], None) for lo, hi in spans]

        return kernel
    from . import fused

    def start(x, spans):
        parity_d, digests_d = fused.encode_and_hash(x, k, m, algo=algo,
                                                    device=device)
        if digests_d is None:
            return (parity_d,), lambda parity: [
                (parity[lo:hi], None) for lo, hi in spans]
        return (parity_d, digests_d), lambda parity, digests: [
            (parity[lo:hi], digests[:, lo:hi]) for lo, hi in spans]

    return _device_kernel(
        start, pad_rows, device,
        functools.partial(fused.encode_hash_program, k, m, algo))


def make_verify_kernel(k: int, m: int, sources: tuple, targets: tuple,
                       algo: str | None, pad_rows: int,
                       device: int | None = None):
    """Fused device verify(+reconstruct) over stacked (B, K, S) gathers
    — the healthy-verify / degraded-decode / heal work item.  A span's
    result is (digests (n, K, hs), the T rebuilt rows or None): the
    rows as T arrays of (n, S) in `targets` order, each a view of the
    array the runtime filled for that target, so a rebuilt byte is
    copied once, by the reader that assembles it, and never here.
    With no targets it is one hash program per algorithm; with targets
    the geometry's one decode program (ops/fused.py), (sources,
    targets) its matrix operand: a matrix a dispatch, so a batch holds
    one pattern (the key carries it) and every pattern runs the same
    executables.  `algo` None: the digest-free decode program of an
    algorithm the host hashes (digests None), one for all of them.
    Both take the ladder."""
    from . import fused

    def start(x, spans):
        digests_d, rows_d = fused.verify_and_transform(
            x, k, m, sources, targets, algo=algo, device=device)
        head = () if digests_d is None else (digests_d,)

        def scatter(*host):
            digests, rows = (host[0] if head else None), host[len(head):]
            return [(None if digests is None else digests[lo:hi],
                     tuple(r[lo:hi] for r in rows) if targets else None)
                    for lo, hi in spans]

        return (*head, *(rows_d or ())), scatter

    kernel = _device_kernel(
        start, pad_rows, device,
        functools.partial(fused.verify_transform_program, k, m, sources,
                          targets, algo))
    if targets:
        kernel.span_tags = {
            "program": fused.verify_transform_name(k, m, algo),
            "targets": len(targets)}
    return kernel


def make_digest_kernel(algo: str, pad_rows: int,
                       device: int | None = None):
    """Batched bitrot digest over stacked (N, S) rows — a healthy GET's
    verify: the device digest program of `algo` (one of
    fused.DEVICE_ALGOS), on lane `device`, sized by the ladder of
    `pad_rows`.  A digest the host computes never rides a lane.  The
    submitter's key carries `pad_rows`, like every parameter a kernel
    closes over."""
    from . import fused

    def start(x, spans):
        out_dev = fused.hash_rows_async(x, algo, device=device)
        return (out_dev,), lambda out: [out[lo:hi] for lo, hi in spans]

    return _device_kernel(start, pad_rows, device,
                          functools.partial(fused.hash_rows_program, algo))


def build_geometry_ladder(k: int, m: int, shard_size: int, algo: str,
                          pad_blocks: int, device: int,
                          padded_blocks: bool = False) -> None:
    """Ask for the ladders of the programs the PUTs and GETs of a set
    of geometry (k, m, shard_size) writing `algo` run on lane `device`,
    each built from the parameters the engine's keys carry: its fused
    encode, its GET digest, and its decode (one program whatever rows a
    read has to rebuild: any pattern names it).  With `padded_blocks`
    (K * shard_size is more than a block: every GET takes the engine's
    generic read) the verify-only hash of that read too, which a
    geometry whose healthy GETs are digested in place meets too seldom
    to pay seconds of compile for.  Where the algorithm hashes on the
    host, the digest-free encode and decode alone: nothing of a device
    digest program."""
    from ..storage import bitrot_io
    from . import fused

    if not m:
        return
    rebuild = (tuple(range(1, k + 1)), (0,))
    if not (algo in fused.DEVICE_ALGOS
            and bitrot_io.device_preferred(algo)):
        build_ladder(make_encode_kernel(k, m, None, pad_blocks, device),
                     (k, shard_size))
        build_ladder(make_verify_kernel(k, m, *rebuild, None, pad_blocks,
                                        device), (k, shard_size))
        return
    build_ladder(make_encode_kernel(k, m, algo, pad_blocks, device),
                 (k, shard_size))
    build_ladder(make_digest_kernel(algo, pad_blocks * k, device),
                 (shard_size,))
    patterns = [rebuild]
    if padded_blocks:
        patterns.append((tuple(range(k)), ()))
    for sources, targets in patterns:
        build_ladder(make_verify_kernel(k, m, sources, targets, algo,
                                        pad_blocks, device),
                     (k, shard_size))


# -- process singleton -------------------------------------------------------

_CO: DispatchCoalescer | None = None
_CO_MU = threading.Lock()

_LANE_SPAN = {st: "lane." + st for st in DispatchLane.STATES}

#: Remote-submit front end (ops/ipc_dispatch.RemoteCoalescer), attached
#: by server/workers.py inside a forked HTTP worker.  When set, every
#: engine call site that does `coalesce.get()` transparently routes
#: remote-eligible keys to the device-owner process and keeps the rest
#: on the worker's own in-process scheduler.
_REMOTE = None


def get():
    r = _REMOTE
    if r is not None:
        return r
    global _CO
    co = _CO
    if co is None:
        with _CO_MU:
            if _CO is None:
                _CO = DispatchCoalescer()
            co = _CO
    return co


def lanes_report() -> list[tuple[int, str, dict[str, float]]]:
    """(device, state it is in, seconds per state) of each lane this
    process has started, for the server's stall report; takes no lock a
    stuck dispatch could hold."""
    co = _CO
    if co is None:
        return []
    return [(d, *ln.state_now()) for d, ln in sorted(co._lanes.items())]


def attach_remote(remote) -> None:
    """Install a cross-process front end as THE coalescer for this
    (worker) process.  detach_remote() restores in-process dispatch."""
    global _REMOTE
    _REMOTE = remote


def detach_remote() -> None:
    global _REMOTE
    r, _REMOTE = _REMOTE, None
    if r is not None:
        r.close()


def reset() -> None:
    """Tests: retire the singleton (its daemon threads exit) so flag
    changes start from a cold scheduler."""
    global _CO
    with _CO_MU:
        if _CO is not None:
            _CO.close()
        _CO = None


def _reset_after_fork() -> None:
    # A forked child inherits the parent's singleton OBJECT but not its
    # dispatcher threads — submits would queue forever.  Drop both the
    # scheduler and any remote front end (its listener thread is gone
    # too); the child lazily builds fresh ones.  The ladder's build
    # thread is the parent's as well: what it had queued is not the
    # child's to build.
    global _CO, _REMOTE, _BUILD_MU, _BUILD_THREAD
    _CO = None
    _REMOTE = None
    _BUILD_MU = threading.Lock()
    _BUILD_THREAD = None
    _BUILD_Q.clear()


os.register_at_fork(after_in_child=_reset_after_fork)

