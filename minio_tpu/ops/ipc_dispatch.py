"""Cross-process kernel dispatch: the remote face of ops/coalesce.py.

In the pre-fork worker pool (server/workers.py) every HTTP worker runs
the full parse/auth/digest/drive-IO vertical, but ONE process — the
device owner — holds JAX/native kernel state and runs the real
`DispatchCoalescer`.  This module is the wire between them:

  worker                          owner
  ------                          -----
  RemoteCoalescer.submit(key,     serve_owner(): pop descriptor,
    payload)                        map the arena slot zero-copy,
    -> write payload into a         rebuild the kernel FROM THE KEY
       ShmArena slot                (kernel_from_key — the coalescer
    -> push a 64B descriptor        contract says the key encodes
       on the request ring          every parameter the kernel closes
    -> return a RemoteHandle        over, which is what makes remote
                                    execution possible at all),
  RemoteHandle.result()             submit to the owner's LOCAL
    <- listener thread pops the     coalescer — cross-WORKER packing
       response descriptor,         happens there — then write the
       copies arrays out of the     result arrays into a response
       response slot, frees it      slot and push a descriptor on the
                                    worker's response ring.

Nothing larger than 64 bytes is ever pickled or queued; shard batches
move through the preallocated arena in place.

Fallback ladder (liveness beats packing, always):
  * arena full / ring full -> compute locally in the worker
    (`DATA_PATH.record_ipc_fallback`);
  * owner heartbeat stale -> fail every pending handle, route
    everything locally until the supervisor respawns the owner under a
    new generation (mirrors PR 5's dispatcher-death contract one level
    up);
  * any per-item owner error -> the handle raises and the engine's
    existing per-request direct fallback recomputes the span.
Each rung is counted.  A worker holds no device (a chip belongs to the
owner), so on a TPU deployment a rung that would compute a DEVICE
kernel in the worker raises instead — the request fails loudly and the
respawned owner serves the retry; host kernels fall back as above.

Routing policy (`MTPU_IPC_DISPATCH`):
  * ``auto`` (default) — only kernels that need the accelerator route
    remotely (single device owner); host-native kernels (ecio put_frame,
    AVX Reed-Solomon, host hashes) already release the GIL inside C and
    scale better N-way in the workers than funneled through one owner;
  * ``all``  — every coalescable kind routes remotely (differential
    tests exercise the full protocol on CPU-only hosts);
  * ``0``    — never (workers behave like MTPU_WORKERS=0 oracles with
    their own in-process coalescers).
"""

from __future__ import annotations

import itertools
import json
import os
import struct
import threading
import time

import numpy as np

from ..observe import span as ospan
from ..observe.metrics import DATA_PATH
from . import coalesce
from .shm_arena import ArenaFull

#: descriptor wire format (one ipc_ring record):
#: magic, worker_id, req_id, slot_off, total_len, hdr_len, status, gen,
#: device — the coalescer-lane index the submitting set is affine to
#: (PR 10), so the owner routes the arena slot to the right device lane
#: without parsing the JSON header.  48 bytes, still inside the 64-byte
#: ring record.
_DESC = struct.Struct("<IIQQQIiII")
_MAGIC = 0x4D545055            # "MTPU"

#: descriptor status codes
ST_REQ = 0                     # request (worker -> owner)
ST_OK = 0                      # response: slot holds hdr+arrays
ST_ERR = 1                     # response: slot holds {"error": ...}
ST_DROP = 2                    # response: no slot (owner overloaded)


def mode() -> str:
    v = os.environ.get("MTPU_IPC_DISPATCH", "auto").strip().lower()
    return v if v in ("auto", "all", "0") else "auto"


def alloc_timeout_s() -> float:
    try:
        return max(0.05,
                   float(os.environ.get("MTPU_IPC_ALLOC_TIMEOUT_S", "2")))
    except ValueError:
        return 2.0


def owner_stale_s() -> float:
    try:
        return max(0.2, float(os.environ.get("MTPU_OWNER_STALE_S", "2")))
    except ValueError:
        return 2.0


# -- kernel registry ----------------------------------------------------------
#
# The coalescer scheduling contract (ops/coalesce.py) requires that a
# key encodes EVERY parameter its kernel closes over — that is what
# lets unrelated requests share one dispatch.  Here it buys more: the
# owner process can rebuild the kernel from the key alone, so no
# callable ever crosses the process boundary.

_CODECS: dict[tuple, object] = {}
_CODEC_MU = threading.Lock()


def _owner_codec(k: int, m: int):
    """The host codec a worker's "nat" encode runs on at the owner."""
    key = (k, m)
    with _CODEC_MU:
        c = _CODECS.get(key)
        if c is not None:
            return c
    from native import rs_comparator
    from native._build import BuildError
    try:
        rs_comparator.load()
        from .erasure_native import ReedSolomonNative
        c = ReedSolomonNative(k, m)
    except BuildError:  # no toolchain: portable codec
        from .erasure import ReedSolomonTPU
        c = ReedSolomonTPU(k, m)
    with _CODEC_MU:
        _CODECS.setdefault(key, c)
        return _CODECS[key]


def _pf_kernel(k: int, m: int, shard_size: int):
    """Owner-side mirror of ErasureSet._pf_kernel (fused host encode)."""
    from ..engine.erasure_set import _ecio_mod
    from ..storage import bitrot_io
    fused_host = _ecio_mod()
    frame_len = bitrot_io.digest_size("mxh256") + shard_size

    def kernel(stacked, spans, ctx):
        nb = stacked.shape[0]
        per = nb * frame_len
        buf = ctx.rent((k + m) * per)
        outs = [buf[i * per:(i + 1) * per] for i in range(k + m)]
        fused_host.put_frame(stacked, k, m, outs=outs)
        return [[o[lo * frame_len:hi * frame_len] for o in outs]
                for lo, hi in spans]

    return kernel


def kernel_from_key(key: tuple, device: int | None = None):
    """Rebuild the dispatch kernel for a coalescer key (placed on lane
    `device` for device-backed kinds).  Raises KeyError for kinds this
    registry does not know (the worker then keeps them local).  The
    device kinds come from the engine's own builders (ops/coalesce.py),
    so owner and engine pad, launch and resolve by one rule."""
    kind = key[0]
    if kind == "digest":
        _, algo, _shard, pad_rows = key
        return coalesce.make_digest_kernel(str(algo), int(pad_rows),
                                           device=device)
    if kind == "pf":
        _, k, m, shard = key
        return _pf_kernel(int(k), int(m), int(shard))
    if kind in ("enc", "vt"):
        from ..engine.erasure_set import BATCH_BLOCKS
    if kind == "enc":
        # The tag is the backend the submitting worker would have used.
        # "dev": the digest-free device program of a host-hashed algo.
        _, tag, k, m, algo, _shard = key
        return coalesce.make_encode_kernel(
            int(k), int(m), str(algo) if tag == "fd" else None,
            BATCH_BLOCKS, device,
            _owner_codec(int(k), int(m)) if tag == "nat" else None)
    if kind == "vt":
        # algo None: the digest-free decode of a host-hashed algorithm.
        _, k, m, sources, targets, algo, _shard = key
        return coalesce.make_verify_kernel(
            int(k), int(m), tuple(sources), tuple(targets),
            None if algo is None else str(algo), BATCH_BLOCKS, device)
    raise KeyError(f"no remote kernel for key kind {kind!r}")


def _key_to_json(key: tuple) -> list:
    return [list(e) if isinstance(e, (tuple, list)) else e for e in key]


def _key_from_json(items: list) -> tuple:
    return tuple(tuple(e) if isinstance(e, list) else e for e in items)


# -- result wire codec --------------------------------------------------------
#
# Results are (lists/tuples of) ndarrays; each kind flattens to an
# ordered list of optional arrays and rebuilds on the worker.

def _flatten_result(kind: str, res):
    if kind == "pf":                 # list of (k+m) equal-length 1-D rows
        return [np.stack([np.asarray(r) for r in res])]
    if kind == "digest":
        return [np.asarray(res)]
    a, b = res
    if kind == "vt":                 # (digests, the T rebuilt rows or None)
        return [a, *(b or ())]
    return [np.asarray(a), None if b is None else np.asarray(b)]   # enc


def _rebuild_result(kind: str, arrays: list):
    if kind == "pf":
        return list(arrays[0])
    if kind == "digest":
        return arrays[0]
    if kind == "vt":
        return arrays[0], tuple(arrays[1:]) or None
    return arrays[0], arrays[1]


def _encode_arrays(arrays: list) -> tuple[bytes, list[np.ndarray]]:
    """-> (header json bytes, arrays to copy after the header)."""
    meta = []
    payload = []
    for a in arrays:
        if a is None:
            meta.append(None)
            continue
        a = np.ascontiguousarray(a)
        meta.append({"shape": list(a.shape), "dtype": str(a.dtype)})
        payload.append(a)
    return json.dumps({"arrays": meta}).encode(), payload


def _decode_arrays(view: np.ndarray, hdr_len: int) -> list:
    meta = json.loads(bytes(view[:hdr_len]))["arrays"]
    out = []
    cur = int(hdr_len)
    for m in meta:
        if m is None:
            out.append(None)
            continue
        dt = np.dtype(m["dtype"])
        shape = tuple(m["shape"])
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        nb = n * dt.itemsize
        # .copy(): the slot is freed as soon as decoding returns.
        out.append(view[cur:cur + nb].view(dt).reshape(shape).copy())
        cur += nb
    return out


# -- worker side --------------------------------------------------------------

class RemoteHandle:
    """Future for one remotely dispatched item — same surface the
    engine already consumes from coalesce.Handle.  Results are copies
    (the arena slot is freed by the listener), so release() has nothing
    pooled to give back."""

    __slots__ = ("_ev", "_res", "_exc", "_t_enq", "_t_done", "_kind",
                 "weight", "nrows")

    def __init__(self, kind: str, weight: int, nrows: int):
        self._ev = threading.Event()
        self._res = None
        self._exc: BaseException | None = None
        self._t_enq = time.monotonic()
        self._t_done: float | None = None
        self._kind = kind
        self.weight = weight
        self.nrows = nrows

    def result(self, timeout: float | None = 120.0):
        with ospan.span("ipc.wait") as sp:
            if not self._ev.wait(timeout):
                raise TimeoutError("remote dispatch did not complete")
            if self._t_done is not None:
                sp.tag(queue_ms=round(
                    max(0.0, self._t_done - self._t_enq) * 1e3, 4))
                self._t_done = None
        if self._exc is not None:
            raise self._exc
        return self._res

    def release(self) -> None:
        pass

    def _finish(self, res=None, exc: BaseException | None = None) -> None:
        self._res = res
        self._exc = exc
        self._t_done = time.monotonic()
        self._ev.set()


class RemoteCoalescer:
    """Worker-process front end: remote-eligible keys ship to the
    device owner; everything else (and every failure) runs on the
    worker's own in-process DispatchCoalescer, which stays the
    correctness oracle."""

    def __init__(self, plane, worker_id: int):
        self.plane = plane
        self.wid = int(worker_id)
        self.local = coalesce.DispatchCoalescer()
        self._mu = threading.Lock()
        self._pending: dict[int, RemoteHandle] = {}
        self._seq = itertools.count(1)
        self._listener: threading.Thread | None = None
        self._stopped = False
        #: owner generation this worker has observed dead (routes local
        #: until the supervisor brings up a NEW generation).
        self._dead_gen = -1
        self.remote_submits = 0
        self.remote_results = 0
        self.remote_errors = 0
        self.fallbacks = 0

    # engine-facing surface ---------------------------------------------------

    def submit(self, key: tuple, payload, fn, weight: int | None = None,
               device: int = 0):
        if not self._remote_eligible(key):
            return self.local.submit(key, payload, fn, weight,
                                     device=device)
        try:
            return self._submit_remote(key, payload, weight, device)
        except Exception:  # noqa: BLE001 — arena/ring full, owner gone
            with self._mu:
                self.fallbacks += 1
            DATA_PATH.record_ipc_fallback()
            return self.local.submit(key, payload, fn, weight,
                                     device=device)

    def lane_stats(self) -> dict:
        return self.local.lane_stats()

    def stats(self) -> dict:
        st = self.local.stats()
        with self._mu:
            st.update({
                "remote_submits": self.remote_submits,
                "remote_results": self.remote_results,
                "remote_errors": self.remote_errors,
                "remote_fallbacks": self.fallbacks,
                "remote_pending": len(self._pending),
                "remote_active": self._remote_active(),
            })
        return st

    def close(self) -> None:
        self._stopped = True
        self._fail_pending(RuntimeError("remote coalescer closed"))
        self.local.close()

    # internals ---------------------------------------------------------------

    def _remote_active(self) -> bool:
        if self.plane is None or mode() == "0":
            return False
        gen = self.plane.owner_gen()
        return self.plane.owner_ok() and gen != self._dead_gen

    def _remote_eligible(self, key: tuple) -> bool:
        m = mode()
        if m == "0" or not self._remote_active():
            return False
        if m == "all":
            return True
        # auto: only accelerator-bound kernels funnel to the single
        # device owner; host-native kernels drop the GIL in C and scale
        # N-way in the workers themselves.
        kind = key[0]
        if kind == "enc":
            return key[1] in ("fd", "dev")
        if kind in ("vt", "digest"):
            return self._device_backend()
        return False

    @staticmethod
    def _device_backend() -> bool:
        # The worker adopted the owner's platform at boot
        # (server/workers.py); it never asks JAX itself.
        from . import devices
        return devices.on_tpu()

    def _submit_remote(self, key: tuple, payload, weight,
                       device: int = 0) -> RemoteHandle:
        payload = np.ascontiguousarray(payload)
        nrows = int(payload.shape[0]) if payload.ndim else 1
        hdr = json.dumps({
            "key": _key_to_json(key),
            "shape": list(payload.shape),
            "dtype": str(payload.dtype),
            "w": int(weight) if weight is not None else nrows,
        }).encode()
        total = len(hdr) + payload.nbytes
        arena = self.plane.arena
        off = arena.alloc(total, timeout=alloc_timeout_s())  # ArenaFull ->
        try:                                                 # caller falls back
            view = arena.view(off, total)
            view[:len(hdr)] = np.frombuffer(hdr, dtype=np.uint8)
            if payload.nbytes:
                view[len(hdr):] = payload.reshape(-1).view(np.uint8)
            h = RemoteHandle(key[0],
                             int(weight) if weight is not None else nrows,
                             nrows)
            req = next(self._seq)
            with self._mu:
                if self._stopped:
                    raise RuntimeError("remote coalescer closed")
                self._pending[req] = h
                self.remote_submits += 1
            rec = _DESC.pack(_MAGIC, self.wid, req, off, total, len(hdr),
                             ST_REQ, self.plane.owner_gen() & 0xFFFFFFFF,
                             int(device) & 0xFFFFFFFF)
            if not self.plane.req_ring.put(rec, timeout=1.0):
                with self._mu:
                    self._pending.pop(req, None)
                raise ArenaFull("request ring full")
        except BaseException:
            arena.free(off, total)
            raise
        self._ensure_listener()
        DATA_PATH.record_ipc_submit(nrows)
        return h

    def _ensure_listener(self) -> None:
        if self._listener is None or not self._listener.is_alive():
            with self._mu:
                if self._listener is None or not self._listener.is_alive():
                    self._listener = threading.Thread(
                        target=self._listen, name="mtpu-ipc-listen",
                        daemon=True)
                    self._listener.start()

    def _listen(self) -> None:
        ring = self.plane.resp_rings[self.wid]
        while not self._stopped:
            rec = ring.get(timeout=0.5)
            if rec is None:
                self._check_owner()
                continue
            try:
                (_, _, req, off, total, hlen, status,
                 _gen, _dev) = _DESC.unpack(rec[:_DESC.size])
            except struct.error:
                continue
            with self._mu:
                h = self._pending.pop(req, None)
            try:
                if h is None:
                    # Stale response for a predecessor of this worker
                    # slot — just return the arena space.
                    continue
                if status == ST_OK:
                    arrays = _decode_arrays(
                        self.plane.arena.view(off, total), hlen)
                    h._finish(res=_rebuild_result(h._kind, arrays))
                    with self._mu:
                        self.remote_results += 1
                    DATA_PATH.record_ipc_result()
                elif status == ST_ERR:
                    msg = "owner dispatch failed"
                    try:
                        msg = json.loads(bytes(
                            self.plane.arena.view(off, total)[:hlen])
                        ).get("error", msg)
                    except Exception:  # noqa: BLE001 — torn header
                        pass
                    h._finish(exc=RuntimeError(msg))
                    with self._mu:
                        self.remote_errors += 1
                else:                  # ST_DROP: no response slot
                    h._finish(exc=RuntimeError(
                        "owner overloaded (no response slot)"))
                    with self._mu:
                        self.remote_errors += 1
            except Exception as e:  # noqa: BLE001 — decode fault
                if h is not None:
                    h._finish(exc=e)
            finally:
                if total and status != ST_DROP:
                    self.plane.arena.free(off, total)

    def _check_owner(self) -> None:
        """Owner-death watchdog: a stale heartbeat fails every pending
        handle NOW (their engine callers fall back to direct compute)
        and pins routing local until a fresh owner generation appears."""
        if self.plane is None or self.plane.owner_ok():
            return
        gen = self.plane.owner_gen()
        if gen == self._dead_gen:
            return
        self._dead_gen = gen
        self._fail_pending(RuntimeError("device owner died"))
        DATA_PATH.record_ipc_owner_death()

    def _fail_pending(self, exc: BaseException) -> None:
        with self._mu:
            victims = list(self._pending.values())
            self._pending.clear()
        for h in victims:
            h._finish(exc=exc)


# -- owner side ---------------------------------------------------------------

def owner_threads() -> int:
    try:
        return max(2, int(os.environ.get("MTPU_IPC_OWNER_THREADS", "4")))
    except ValueError:
        return 4


def serve_owner(plane, stop, co=None, nthreads: int | None = None) -> list:
    """Run the owner service: a small pool of reader threads, each
    popping request descriptors and carrying one item through
    submit -> result -> respond.  Multiple readers are what lets the
    owner's LOCAL coalescer pack items from different WORKERS into one
    kernel launch.  Returns the thread list; `stop` is a
    threading.Event the caller sets to retire the service."""
    co = co or coalesce.get()
    threads = []
    for i in range(nthreads or owner_threads()):
        t = threading.Thread(target=_owner_loop, args=(plane, stop, co),
                             name=f"mtpu-ipc-owner-{i}", daemon=True)
        t.start()
        threads.append(t)
    return threads


def _owner_loop(plane, stop, co) -> None:
    while not stop.is_set():
        rec = plane.req_ring.get(timeout=0.25)
        if rec is None:
            continue
        try:
            _serve_one(plane, co, rec)
        except Exception:  # noqa: BLE001 — never kill the service loop
            pass


def _serve_one(plane, co, rec: bytes) -> None:
    try:
        (magic, wid, req, off, total, hlen, _status,
         _gen, dev) = _DESC.unpack(rec[:_DESC.size])
    except struct.error:
        return
    if magic != _MAGIC:
        return
    kind = ""
    try:
        view = plane.arena.view(off, total)
        meta = json.loads(bytes(view[:hlen]))
        key = _key_from_json(meta["key"])
        kind = key[0]
        shape = tuple(meta["shape"])
        dt = np.dtype(meta["dtype"])
        payload = view[hlen:].view(dt).reshape(shape)
        # Route to the lane the submitting set is affine to: the owner
        # packs cross-WORKER traffic per DEVICE, not into one queue.
        fn = kernel_from_key(key, device=dev)
        h = co.submit(key, payload, fn, weight=meta.get("w"),
                      device=dev)
        res = h.result(timeout=120.0)
        arrays = _flatten_result(kind, res)
        hdr, copies = _encode_arrays(arrays)
    except Exception as e:  # noqa: BLE001 — report, don't die
        plane.arena.free(off, total)
        _respond_error(plane, wid, req, e)
        return
    try:
        _respond_ok(plane, wid, req, hdr, copies, freeing=(off, total))
    finally:
        # Release only after the response bytes were copied out — pf
        # results alias the dispatch's pooled scratch buffer.
        h.release()


def _respond_ok(plane, wid, req, hdr: bytes, arrays: list[np.ndarray],
                freeing: tuple) -> None:
    rtotal = len(hdr) + sum(a.nbytes for a in arrays)
    try:
        roff = plane.arena.alloc(rtotal, timeout=2.0)
    except ArenaFull:
        plane.arena.free(*freeing)
        _push_resp(plane, wid,
                   _DESC.pack(_MAGIC, wid, req, 0, 0, 0, ST_DROP, 0, 0))
        return
    view = plane.arena.view(roff, rtotal)
    view[:len(hdr)] = np.frombuffer(hdr, dtype=np.uint8)
    cur = len(hdr)
    for a in arrays:
        if a.nbytes:
            view[cur:cur + a.nbytes] = a.reshape(-1).view(np.uint8)
        cur += a.nbytes
    # The request slot is only reusable once the result no longer
    # aliases pooled dispatch buffers — everything above was copied.
    plane.arena.free(*freeing)
    rec = _DESC.pack(_MAGIC, wid, req, roff, rtotal, len(hdr), ST_OK,
                     0, 0)
    if not _push_resp(plane, wid, rec):
        plane.arena.free(roff, rtotal)


def _respond_error(plane, wid, req, exc: BaseException) -> None:
    hdr = json.dumps({"error": f"{type(exc).__name__}: {exc}"[:400]}).encode()
    try:
        roff = plane.arena.alloc(len(hdr), timeout=1.0)
    except ArenaFull:
        _push_resp(plane, wid,
                   _DESC.pack(_MAGIC, wid, req, 0, 0, 0, ST_DROP, 0, 0))
        return
    view = plane.arena.view(roff, len(hdr))
    view[:] = np.frombuffer(hdr, dtype=np.uint8)
    rec = _DESC.pack(_MAGIC, wid, req, roff, len(hdr), len(hdr), ST_ERR,
                     0, 0)
    if not _push_resp(plane, wid, rec):
        plane.arena.free(roff, len(hdr))


def _push_resp(plane, wid: int, rec: bytes) -> bool:
    try:
        return plane.resp_rings[wid].put(rec, timeout=2.0)
    except Exception:  # noqa: BLE001 — ring torn down mid-shutdown
        return False
