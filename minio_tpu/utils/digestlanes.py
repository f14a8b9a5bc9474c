"""Shared multi-buffer digest lane scheduler (MTPU_NATIVE_DIGEST).

MD5 is serial *within* one stream, but the S3 data plane runs many
independent digest streams at once — concurrent PUT ETags, multipart
part ETags, Content-MD5 verification.  native/digest.cc steps N
incremental MD5 states through SIMD lanes in lockstep (AVX2 8-wide /
SSE2 4-wide), so the aggregate rate on one core is lane-parallel.  This
module owns the process-wide scheduler that multiplexes PipelinedMD5
streams onto those shared lanes:

  * producers append pieces to their stream (zero-copy: immutable
    views are held, not copied; a writable one is held too where its
    owner lends it and waits for `wait_consumed` before it overwrites,
    as the PUT-ingest ring of utils/streams.py does, and copied
    otherwise);
  * one worker thread carves 64-byte-aligned runs from EVERY active
    stream and advances them all in ONE GIL-released native call;
  * finalize appends the RFC 1321 padding into the same lockstep call,
    so a stream's digest is ready one tick after its last byte.

MTPU_NATIVE_DIGEST=0 (or an unbuildable native lib) disables the plane;
callers fall back to hashlib and produce byte-identical digests — the
differential oracle the tests pin.

Env knobs:
  MTPU_NATIVE_DIGEST      1 (default) native lanes, 0 hashlib oracle
  MTPU_DIGEST_TICK_CAP    max bytes carved per stream per tick (8 MiB)
  MTPU_DIGEST_MAX_PENDING per-stream backpressure bound (64 MiB)
"""

from __future__ import annotations

import hashlib
import os
import threading
from time import monotonic as _now

_MD5_INIT = (0x67452301, 0xEFCDAB89, 0x98BADCFE, 0x10325476)

_native_mod = None
_native_state = None       # None = unprobed, True/False after first probe
_probe_mu = threading.Lock()


def enabled() -> bool:
    """The MTPU_NATIVE_DIGEST flag alone (not whether the lib builds)."""
    return os.environ.get("MTPU_NATIVE_DIGEST", "1") != "0"


def native_available() -> bool:
    """True once native/digest.cc built and loaded (probed once)."""
    global _native_mod, _native_state
    if _native_state is None:
        with _probe_mu:
            if _native_state is None:
                from native import digest_native
                from native._build import BuildError
                try:
                    digest_native.load()
                    _native_mod = digest_native
                    _native_state = True
                except BuildError:  # no toolchain: hashlib serves
                    _native_state = False
    return _native_state


def use_native() -> bool:
    return enabled() and native_available()


class _Stream:
    __slots__ = ("pieces", "carry", "total", "pending", "consumed",
                 "busy", "finalizing", "row", "done", "result", "error")

    def __init__(self, row: int):
        self.pieces: list = []
        self.carry = b""
        self.total = 0
        self.pending = 0           # bytes queued but not yet hashed
        self.consumed = 0          # leading bytes no piece is held of
        self.busy = False          # a tick in flight holds pieces of it
        self.finalizing = False
        self.row = row
        self.done = threading.Event()
        self.result: bytes | None = None
        self.error: BaseException | None = None


class LaneScheduler:
    """One worker thread owning the native MD5 lane states; every tick
    advances ALL active streams in a single GIL-released call."""

    def __init__(self):
        from native import digest_native as dn
        import numpy as np

        from ..observe.metrics import DATA_PATH
        self._dn = dn
        self._np = np
        self._dp = DATA_PATH
        dn.load()
        self.lanes = dn.md5_lanes()
        self._cv = threading.Condition()
        self._streams: set[_Stream] = set()
        self._cap = 16
        self._states = np.empty((self._cap, 4), dtype=np.uint32)
        self._free = list(range(self._cap))
        # a row the worker's in-flight native call is writing (its
        # stream is `busy`) is given back only at tick end, so open()
        # can never hand it to a new stream while the (lock-free)
        # native update still targets it
        self._deferred_free: list[int] = []
        self._thread: threading.Thread | None = None
        self._tick_cap = int(os.environ.get(
            "MTPU_DIGEST_TICK_CAP", str(8 << 20)))
        self._max_pending = int(os.environ.get(
            "MTPU_DIGEST_MAX_PENDING", str(64 << 20)))

    # -- producer side -------------------------------------------------------

    def open(self) -> _Stream:
        with self._cv:
            if not self._free:
                # grow the state table; existing row indices stay valid
                ncap = self._cap * 2
                ns = self._np.empty((ncap, 4), dtype=self._np.uint32)
                ns[:self._cap] = self._states
                self._free.extend(range(self._cap, ncap))
                self._states = ns
                self._cap = ncap
            row = self._free.pop()
            self._states[row] = _MD5_INIT
            s = _Stream(row)
            self._streams.add(s)
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, name="mtpu-digest-lanes", daemon=True)
                self._thread.start()
            return s

    def update(self, s: _Stream, piece, lent: bool = False) -> None:
        """Queue `piece` behind what the stream already holds.  The
        scheduler keeps the object until a tick has hashed it, so what
        may change underneath is copied first (a bytearray, a writable
        view) — unless it is `lent`: the caller then leaves the bytes
        alone until `wait_consumed` says the scheduler is done with
        them."""
        if not isinstance(piece, (bytes, memoryview)) or (
                isinstance(piece, memoryview) and not piece.readonly
                and not lent):
            piece = bytes(piece)
            self._dp.record_put_fresh_buffer(len(piece))
        with self._cv:
            while (s.pending > self._max_pending and not s.finalizing
                   and s.error is None):
                self._cv.wait(timeout=1.0)
            s.total += len(piece)
            if s.error is not None:         # hashes no further: not kept
                if not s.busy:
                    s.consumed = s.total
                return
            if not len(piece):
                return
            s.pieces.append(piece)
            s.pending += len(piece)
            self._cv.notify_all()

    def wait_consumed(self, s: _Stream, upto: int) -> None:
        """Block until the first `upto` bytes handed to `update` are
        hashed (or copied into the stream's sub-block carry): no tick
        reads those pieces' memory any more, so their owner may
        overwrite or free it.  This wait, and nothing about ring depth
        or the lanes' speed, is what makes a lent piece safe."""
        with self._cv:
            while s.consumed < upto:
                self._cv.wait()

    def finalize_async(self, s: _Stream) -> None:
        """Ask the worker to pad+close the stream without waiting for
        the result — the PipelinedMD5.close() contract: on the success
        path the digest finishes under the caller's remaining work, on
        the failure path the row is freed either way."""
        with self._cv:
            if not s.finalizing:
                s.finalizing = True
                self._cv.notify_all()

    def digest(self, s: _Stream) -> bytes:
        self.finalize_async(s)
        s.done.wait()
        if s.error is not None:
            raise s.error
        return s.result

    def drain(self, timeout: float = 1.0) -> bool:
        """Bounded wait for the lane set to empty (graceful shutdown):
        every stream already has finalize_async pending or belongs to a
        request the server drained, so this is normally instant.  A
        stream that never finalizes only costs the timeout."""
        deadline = _now() + timeout
        with self._cv:
            while self._streams:
                left = deadline - _now()
                if left <= 0:
                    return False
                self._cv.wait(timeout=min(left, 0.1))
        return True

    def abandon(self, s: _Stream) -> None:
        """Drop a stream without a digest (failed PUT)."""
        with self._cv:
            if s in self._streams:
                self._streams.discard(s)
                if s.busy:
                    self._deferred_free.append(s.row)
                else:
                    self._free.append(s.row)
                s.error = RuntimeError("digest stream abandoned")
                if not s.busy:
                    self._drop_locked(s)    # else: at that tick's end
                s.done.set()
                self._cv.notify_all()

    @staticmethod
    def _drop_locked(s: _Stream) -> None:
        """A failed or abandoned stream hashes no further: let go of
        what it still has queued.  Only while no tick holds pieces of
        it (`consumed` is a prefix: it may not pass a piece in
        flight)."""
        s.pending -= sum(len(p) for p in s.pieces)
        s.pieces.clear()
        s.consumed = s.total

    # -- worker side ---------------------------------------------------------

    def _run(self) -> None:
        while True:
            with self._cv:
                work = self._collect_locked()
                while not work:
                    self._cv.wait()
                    work = self._collect_locked()
                states = self._states
                nrows = self._cap
                for s, *_ in work:
                    s.busy = True
            chunks = [b""] * nrows
            closing = []
            for s, pieces, carry, finalizing, total in work:
                full = carry + b"".join(pieces) if (carry or len(pieces) != 1) \
                    else pieces[0]
                if finalizing:
                    nb = len(full) // 64 * 64
                    if nb and isinstance(full, (bytes, memoryview)):
                        # large final flush: hash the aligned prefix
                        # zero-copy this tick; the <64B pad-bearing
                        # tail closes the stream on the next tick
                        chunks[s.row] = memoryview(full)[:nb]
                        rest = bytes(full[nb:])
                        with self._cv:
                            s.carry = rest
                            s.pending += len(rest)
                    else:
                        chunks[s.row] = (bytes(memoryview(full)[:nb])
                                         + self._dn.md5_pad(
                                             bytes(full[nb:]), total))
                        closing.append((s, total))
                else:
                    nb = len(full) // 64 * 64
                    if nb == len(full) and isinstance(full, (bytes,
                                                             memoryview)):
                        chunks[s.row] = full
                        rest = b""
                    else:
                        # memoryview: the aligned prefix of an already-
                        # materialized join must not cost a second copy
                        chunks[s.row] = memoryview(full)[:nb]
                        rest = bytes(full[nb:])
                    with self._cv:
                        s.carry = rest
                        s.pending += len(rest)
            nbytes = sum(len(c) for c in chunks)
            err = None
            try:
                if nbytes:
                    self._dn.md5_update_mb(states, chunks)
            except BaseException as e:      # native fault: fail streams
                err = e
            self._dp.record_digest_batch(len(work), nbytes)
            with self._cv:
                if self._states is not states:
                    # open() grew the table mid-tick: it copied the
                    # PRE-update rows into the new array, so merge the
                    # rows the native call just advanced back in.  Row
                    # reuse is blocked while in flight (_deferred_free),
                    # so every work row still belongs to its stream.
                    for s, *_ in work:
                        self._states[s.row] = states[s.row]
                for s, pieces, carry, finalizing, total in work:
                    # pending tracks queued-but-unhashed bytes: the
                    # whole collected run is consumed here, and any
                    # unhashed remainder was re-added when s.carry was
                    # set during assembly
                    taken = sum(len(p) for p in pieces)
                    s.pending -= taken + len(carry)
                    s.consumed += taken
                    s.busy = False
                    if err is not None:
                        s.error = err
                    if s.error is not None:
                        self._drop_locked(s)
                for s, total in closing:
                    if s in self._streams:
                        self._streams.discard(s)
                        self._free.append(s.row)
                        if err is None:
                            s.result = self._dn.md5_finalize(
                                self._states[s.row], total)
                        s.done.set()
                self._free.extend(self._deferred_free)
                self._deferred_free.clear()
                self._cv.notify_all()

    def _collect_locked(self):
        """Carve pending work under the lock; assembly happens outside.
        Returns [(stream, pieces, carry, finalizing, total)]."""
        work = []
        for s in list(self._streams):
            avail = len(s.carry) + sum(len(p) for p in s.pieces)
            if s.finalizing or avail >= 64:
                take, taken = [], 0
                while s.pieces and (taken < self._tick_cap or s.finalizing):
                    p = s.pieces.pop(0)
                    take.append(p)
                    taken += len(p)
                if s.finalizing or take or len(s.carry) >= 64:
                    carry = s.carry
                    s.carry = b""
                    work.append((s, take, carry, s.finalizing, s.total))
            elif s.pieces:
                # Under one block in all: keep the bytes, not the
                # pieces, so that nobody waits (`wait_consumed`) for a
                # tick that only more bytes or a finalize would bring.
                s.consumed += avail - len(s.carry)
                s.carry += b"".join(s.pieces)
                s.pieces.clear()
                self._cv.notify_all()
        return work


_SCHED: LaneScheduler | None = None
_sched_mu = threading.Lock()


def scheduler() -> LaneScheduler:
    global _SCHED
    if _SCHED is None:
        with _sched_mu:
            if _SCHED is None:
                _SCHED = LaneScheduler()
    return _SCHED


def drain(timeout: float = 1.0) -> bool:
    """Flush the process-wide scheduler if one exists (graceful drain
    path); True when no streams remain.  Never instantiates lanes."""
    s = _SCHED
    if s is None:
        return True
    return s.drain(timeout)


def _reset_after_fork() -> None:
    # A forked worker inherits the scheduler object but NOT its ticker
    # thread — any stream enqueued in the child would hang, and the
    # inherited lock may be held by a parent thread that doesn't exist
    # here.  Drop the singleton; the child lazily builds its own lanes.
    global _SCHED, _sched_mu
    _SCHED = None
    _sched_mu = threading.Lock()


os.register_at_fork(after_in_child=_reset_after_fork)


# -- one-shot helpers (the "rides the same plane" entries) -------------------

def md5_digest(data) -> bytes:
    """MD5 of one in-memory buffer through the digest plane: on the
    native path this shares lanes with every concurrent ETag stream
    (Content-MD5 verification batches with in-flight PUTs); the oracle
    is plain hashlib."""
    if use_native():
        sched = scheduler()
        s = sched.open()
        try:
            mv = memoryview(data)
            for off in range(0, len(mv), 1 << 20):
                sched.update(s, mv[off:off + (1 << 20)])
            return sched.digest(s)
        finally:
            sched.abandon(s)
    return hashlib.md5(data).digest()


def sha256_many(bufs) -> list[bytes]:
    """SHA256 of many buffers: ONE GIL-released native batch call
    (SHA-NI pairs when available) vs per-buffer hashlib on the oracle
    path.  A single buffer stays on hashlib — OpenSSL's single-stream
    SHA-NI is already optimal and the batch entry only wins when it can
    pair streams or amortize the call."""
    if len(bufs) >= 2 and use_native():
        from ..observe.metrics import DATA_PATH
        out = _native_mod.sha256_batch(bufs)
        DATA_PATH.record_sha_batch(len(bufs), sum(len(b) for b in bufs))
        return out
    return [hashlib.sha256(b).digest() for b in bufs]
