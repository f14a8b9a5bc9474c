"""One erasure set's shard math, and where it runs.

The engine (engine/erasure_set.py) and the healer (engine/heal.py) ask
one `ShardMath` (an `ErasureSet`'s `math`) for what they need in their
terms: blocks in, framed shards, digests or rebuilt rows out.  Behind
it lives the one decision *which plane computes this, and does the
work ride the coalescer*:

- the plane: the fused host kernel (native/ecio.cc, one C pass), the
  set's device lane (the fused programs of ops/fused.py; for an
  algorithm the host hashes, their digest-free forms, the digests
  computed on the thread that holds the rows), the mesh
  (parallel/sharded.py, `mesh_rule`), or the native host codec;
- coalesced (ops/coalesce.py, MTPU_COALESCE) or direct.  Each plane has
  ONE direct implementation: it serves MTPU_COALESCE=0 and is what a
  failed coalescer handle falls back to (`_settle` counts it).

Every predicate is read per operation (tests flip MTPU_MESH,
MTPU_COALESCE and MTPU_DEVICES at runtime; a pool worker's
`coalesce.get()` is its remote front end); nothing outlives one PUT
stream (`Encoder`).  The coalescer keys, the kernel factories'
arguments and `device=` are what boot's ladder was built from
(`build_ladder`): change one and a program compiles at first sight,
under load.
"""

from __future__ import annotations

import os
import threading
import weakref

import numpy as np

from ..observe import span as ospan
from ..observe.metrics import DATA_PATH
from ..ops import coalesce, devcache, fused
from ..ops import devices as devices_mod
from ..ops.erasure_cpu import ReedSolomonCPU
from ..ops.erasure_jax import ReedSolomonTPU
from ..storage import bitrot_io

BLOCK_SIZE = 1 << 20          # blockSizeV2, cmd/object-api-common.go:40
BATCH_BLOCKS = 32             # 1 MiB blocks per device dispatch (32 MiB data)


def platform() -> tuple[bool, bool]:
    """(the deployment's shard math runs on a TPU, this process holds
    it): the seam's one platform predicate, from ops/devices.  A pool
    worker answers (True, False): it adopted its owner's platform and
    its work rides the owner's lanes.  Tests patch this to run the
    device codec on the CPU backend."""
    return devices_mod.on_tpu(), devices_mod.local_tpu()


# Whether the native host codec built + loaded (None = untried).
_NATIVE_OK: bool | None = None

# Fused host erasure-IO kernel (native/ecio.cc): encode+hash+frame /
# verify+gather+reconstruct in one C pass (None = untried, False = n/a).
_ECIO = None


def ecio_mod():
    global _ECIO
    if _ECIO is None:
        from native import ecio_native
        from native._build import BuildError
        try:
            ecio_native.load()
            _ECIO = ecio_native
        except BuildError:  # no toolchain: numpy paths serve
            _ECIO = False
    return _ECIO or None


# Process-wide mesh for multi-device codec placement (built lazily).
_MESH = None

# Per-thread pair of framing buffers, reused batch after batch and
# stream after stream: a page of fresh anonymous memory costs ~10 us at
# first touch on the chip's host and glibc maps an allocation this
# large anew every time, so a fresh ~50 MB output a batch is ~120 ms of
# page faults, twice a 64 MiB part (PERF.md §6, PR 34 and PR 36).  Each
# grows to the largest batch its thread has framed, is never zeroed and
# lives while the thread does (a connection's request thread: at 8+4
# two 50.3 MB buffers, one where no stream of the thread ran past one
# batch).  One encode per thread at a time; the callers' write
# fan-outs return only after every drive call has (`list(pool.map)` or
# inline in `_map_drives_positions`; no drive call is abandoned on a
# timeout: the health wrapper times a call on its own thread and an
# RPC drive's timeout raises inside it), and StagePipeline joins its
# in-flight write before it returns, so nobody reads a buffer when it
# comes round again.
_DB_ARENAS = threading.local()


def _db_arena(slot: int, nbytes: int) -> np.ndarray:
    """Framing buffer `slot` (0 or 1) of the calling thread, at least
    `nbytes` long.  What had to be allocated is counted
    (mtpu_put_fresh_buffer_bytes_total); reuse is not."""
    pair = getattr(_DB_ARENAS, "pair", None)
    if pair is None:
        pair = _DB_ARENAS.pair = [None, None]
    arena = pair[slot]
    if arena is None or arena.size < nbytes:
        arena = pair[slot] = np.empty(nbytes, dtype=np.uint8)
        DATA_PATH.record_put_fresh_buffer(nbytes)
    return arena


# The erasure sets this process serves, by their `ShardMath` (weak: a
# set that is dropped stops counting), for the mesh rule below.
_LOCAL_SETS: "weakref.WeakSet[ShardMath]" = weakref.WeakSet()
_LOCAL_SETS_MU = threading.Lock()


def _chips_with_a_set() -> int:
    """How many of the process's lanes own an erasure set it serves
    (`device_idx` is set index % lanes, so four sets own four lanes)."""
    with _LOCAL_SETS_MU:
        live = list(_LOCAL_SETS)
    return len({sm.device_idx for sm in live})


def mesh_rule(local_tpu: bool, chips: int, sets: int,
              forced: str = "") -> bool:
    """Whether codec work is spread over a multi-device mesh, from what
    the process can observe: the platform, the chips it holds and the
    chips that already own an erasure set it serves (`sets`).

    Where every chip owns a set, a set's shard math rides its own lane:
    the fused, coalesced, laddered, pre-built dispatch a one-chip host
    runs (ops/coalesce.py), four of them side by side.  The mesh
    (parallel/sharded.py) is for chips that would otherwise sit by:
    they outnumber the sets that own one.  A pool worker holds no chip,
    and a host backend has no mesh worth its collectives.  `forced` is
    MTPU_MESH: "1"/"0" override (tests: the SPMD path on the virtual
    CPU mesh)."""
    if forced == "1":
        return True
    if forced == "0":
        return False
    return local_tpu and chips > 1 and sets < chips


def mesh_mode() -> bool:
    """`mesh_rule` of this process, now (the WithAutoGoroutines role,
    cmd/erasure-coding.go:63: scaling without configuration).  Read per
    call: tests flip MTPU_MESH and MTPU_DEVICES at runtime, and the
    count of live sets grows while engine/sets.py constructs them."""
    forced = os.environ.get("MTPU_MESH", "")
    if forced in ("0", "1") or not platform()[1]:
        # Decided without counting (forced: without asking JAX either).
        return mesh_rule(False, 0, 0, forced)
    return mesh_rule(True, devices_mod.visible_count(),
                     _chips_with_a_set(), forced)


def _settle(h, direct):
    """Wait for a coalesced handle.  A handle can FAIL (a poisoned
    batch neighbor, a dead dispatcher): its span is then recomputed by
    `direct`, the plane's direct implementation — this request's
    bytes, this request's kernels, nobody else's fault surface — and
    the fallback is counted.  Returns (result, the handle or None after
    a fallback): the caller releases the handle once it has consumed
    what the result aliases."""
    try:
        return h.result(), h
    except Exception:  # noqa: BLE001 — direct fallback
        DATA_PATH.record_co_fallback()
        return direct(), None


class ShardMath:
    """The shard math of one erasure set (`ErasureSet.math`)."""

    def __init__(self, set_index: int = 0):
        self.set_index = set_index
        self._codecs: dict[tuple, object] = {}   # (kind, k, m) -> codec
        with _LOCAL_SETS_MU:
            _LOCAL_SETS.add(self)

    # -- the choice ----------------------------------------------------------

    @property
    def device_idx(self) -> int:
        """The coalescer lane (device) this set's kernel traffic rides:
        `set_index % n_devices`, the set's sipHashMod placement one
        layer down — stable across boots, the same in every process.
        Resolved per call: tests flip MTPU_DEVICES at runtime."""
        return devices_mod.device_for_set(self.set_index)

    @property
    def use_device(self) -> bool:
        """Device codec on a real TPU; native AVX codec otherwise (off
        a TPU the XLA-CPU bit-plane path would be the bottleneck).  A
        pool worker holds the device owner's answer."""
        return platform()[0]

    def _fused_dev(self, algo: str) -> bool:
        """Parity (or rebuilt rows) AND bitrot digests in one device
        program: the algorithm has one and prefers it to its host
        kernel (bitrot_io.device_preferred)."""
        return (algo in fused.DEVICE_ALGOS and self.use_device
                and bitrot_io.device_preferred(algo))

    def host_fused(self, k: int, m: int, algo: str | None = None):
        """The fused host kernels (native/ecio.cc: `put_frame`,
        `get_verify` over mmap'd frames, `gf_transform_rows`), or None
        where the shard math does not run there.  ONE native pass per
        batch: parity or verify + gather + reconstruct, digests, frame
        layout.  `algo` None asks for the digest-free row transform
        alone.  Width-gated: the C kernels hold at most 64 row pointers
        on the stack."""
        if (not self.use_device and k + m <= 64 and (
                algo is None or (algo == "mxh256" and not mesh_mode()))):
            return ecio_mod()
        return None

    def segment_blocks(self) -> int:
        """Blocks of a GET segment: one bounded dispatch on the device;
        on the host 16 MiB, which keeps the gather buffer under glibc's
        mmap threshold (a fresh 32 MiB pays ~0.5 ms/MiB in faults)."""
        return BATCH_BLOCKS if self.use_device else BATCH_BLOCKS // 2

    @staticmethod
    def _co():
        """The coalescer an operation rides (a pool worker's: its remote
        front end, ops/ipc_dispatch.py), or None (MTPU_COALESCE=0)."""
        return coalesce.get() if coalesce.enabled() else None

    # -- codecs --------------------------------------------------------------

    def _cached(self, kind: str, k: int, m: int, make):
        codec = self._codecs.get((kind, k, m))
        if codec is None:
            codec = self._codecs[kind, k, m] = make(k, m)
        return codec

    def cpu(self, k: int, m: int) -> ReedSolomonCPU:
        """The CPU oracle codec (tails: a partial block is tiny, not
        worth a dispatch)."""
        return self._cached("cpu", k, m, ReedSolomonCPU)

    def _codec(self, k: int, m: int) -> ReedSolomonTPU:
        return self._cached("tpu", k, m, ReedSolomonTPU)

    def native(self, k: int, m: int):
        """Host codec: the native AVX kernel, or the portable XLA path
        on a host with no toolchain to build it.  A kernel that built
        and does not load is an error, not a reason to degrade."""
        def make(k, m):
            global _NATIVE_OK
            if _NATIVE_OK is None:
                from native import rs_comparator
                from native._build import BuildError
                try:
                    rs_comparator.load()
                    _NATIVE_OK = True
                except BuildError:  # no toolchain
                    _NATIVE_OK = False
            if not _NATIVE_OK:
                return self._codec(k, m)
            from ..ops.erasure_native import ReedSolomonNative
            return ReedSolomonNative(k, m)
        return self._cached("native", k, m, make)

    def _sharded(self, k: int, m: int):
        """Mesh codec (parallel/sharded.py) per geometry over the
        process-wide device mesh."""
        def make(k, m):
            global _MESH
            from ..parallel.sharded import ShardedCodec, make_mesh
            if _MESH is None:
                _MESH = make_mesh()
            return ShardedCodec(k, m, _MESH)
        return self._cached("sharded", k, m, make)

    def _on_mesh(self, k: int, m: int, x, tiled: int, run):
        """`run(codec, x)` over the mesh, the batch padded to its block
        axis; None when axis `tiled` of `x` doesn't tile over the lanes
        (the caller falls back to the single-device path)."""
        sc = self._sharded(k, m)
        x = np.asarray(x)
        if x.shape[tiled] % sc.mesh.shape["lanes"]:
            return None
        nb = x.shape[0]
        pad = (-nb) % sc.mesh.shape["blocks"]
        if pad:
            x = np.concatenate(
                [x, np.zeros((pad,) + x.shape[1:], np.uint8)])
        return np.asarray(run(sc, x))[:nb]

    # -- coalesced-dispatch kernels (ops/coalesce.py) ------------------------
    #
    # Each factory returns an fn(stacked, spans, ctx) closure computing
    # one coalesced batch; the coalescer key carries every parameter the
    # closure captures, so items of different requests (and of different
    # sets of one geometry) stack along the block axis.

    @staticmethod
    def _pf_kernel(k: int, m: int, shard_size: int):
        """Fused host encode (ecio put_frame) over the stacked blocks,
        into a pooled per-dispatch buffer (a fresh mmap-sized one per
        dispatch would pay ~0.5 ms/MiB in page faults; the direct
        path's per-thread arena cannot be aliased across requests).
        Shard i's frames are contiguous, so item j's framed views are
        plain slices."""
        fused_host = ecio_mod()
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

    def enc_kernel(self, k: int, m: int, algo: str, fused_dev: bool,
                   device: int | None = None):
        """Device/native encode over the stacked blocks (ops/coalesce
        .make_encode_kernel), a device batch sized by the ladder of
        BATCH_BLOCKS: (parity, digests) per span, the pair
        `direct_encode` gives; on a chip without `fused_dev` the
        digest-free program (digests None: the framing pass hashes).
        `device`: the submitting set's lane."""
        if self.use_device:
            return coalesce.make_encode_kernel(
                k, m, algo if fused_dev else None, BATCH_BLOCKS, device)
        return coalesce.make_encode_kernel(
            k, m, algo, BATCH_BLOCKS, device, self.native(k, m))

    @staticmethod
    def vt_kernel(k: int, m: int, sources: tuple, targets: tuple,
                  algo: str | None, device: int | None = None):
        """Fused device verify(+reconstruct) over stacked (B, K, S)
        gathers (ops/coalesce.make_verify_kernel); `algo` None the
        digest-free rebuild.  `device` places the dispatch on the
        submitting set's affine lane."""
        return coalesce.make_verify_kernel(k, m, sources, targets, algo,
                                           BATCH_BLOCKS, device)

    def build_ladder(self, k: int, m: int) -> None:
        """Ask for the shape ladder of the device programs this set's
        PUTs and GETs run at (k, m), on its lane: the fused encode, the
        GET digest and the decode of the write algorithm, and where K
        does not divide the block (every GET then takes the generic
        read) its verify-only hash; for an algorithm the host hashes,
        the digest-free encode and decode alone.  Built off the calling
        thread (ops/coalesce.build_ladder); nothing on the host."""
        if not self.use_device:
            return
        coalesce.build_geometry_ladder(
            k, m, -(-BLOCK_SIZE // k), bitrot_io.write_algo(),
            BATCH_BLOCKS, self.device_idx,
            padded_blocks=BLOCK_SIZE % k != 0)

    # -- operations ----------------------------------------------------------

    def encoder(self, k: int, m: int, algo: str,
                double_buffer: bool = False) -> "Encoder":
        """The encode of one PUT stream (see `Encoder`)."""
        return Encoder(self, k, m, algo, double_buffer)

    def direct_encode(self, blocks, k: int, m: int, algo: str):
        """The no-coalescer encode for one (nb, K, S) batch — the same
        (parity, digests) pair `enc_kernel` produces.  Dispatched, not
        waited for: `Encoder.frames` is the sync point."""
        if self._fused_dev(algo):
            # Parity AND bitrot digests in ONE device dispatch
            # (ops/fused.py); framing is then byte interleaving.
            return fused.encode_and_hash(blocks, k, m, algo=algo,
                                         device=self.device_idx)
        if self.use_device:
            # Host-hashed algorithms (sha256, HighwayHash with its
            # native kernel): device encodes, the framing pass hashes.
            return self._codec(k, m).encode_blocks(
                devices_mod.put(blocks, self.device_idx)), None
        # No TPU: native AVX codec; the framing pass hashes.
        return self.native(k, m).encode_blocks(blocks), None

    def digest_rides(self, nb: int, algo: str) -> bool:
        """Whether a healthy GET's digests of `algo` ride the set's
        lane: only where the kernel is a device program
        (`_fused_dev`), so concurrent GETs share a launch.  A digest
        the host computes runs on the thread that holds its rows,
        whatever the lane is doing: a host kernel on the lane would
        share no launch, only add a stacking copy and one serial
        thread.  Byte-exact either way."""
        return (self._co() is not None and nb > 0
                and self._fused_dev(algo))

    def digest(self, y: np.ndarray, k: int, m: int, algo: str,
               rides: bool) -> np.ndarray | None:
        """Bitrot digests (nb, k, hs) of a healthy GET's gathered rows
        `y` (nb, k, S), or None: this plane hashes the frames where
        they lie, on the calling thread, with the host kernels
        (`_hash_shard_frames`).  `rides` is `digest_rides(nb, algo)`,
        asked once by the caller."""
        nb, _, shard_size = y.shape
        co = self._co() if rides else None
        if co is not None:
            # Over the already-gathered rows (the gather IS the
            # assembly: no copy), stacked with other requests' digest
            # work into one device hash program, sized by the ladder
            # of BATCH_BLOCKS * k rows.
            flat = y.reshape(nb * k, shard_size)
            pad_rows = BATCH_BLOCKS * k
            return self._ride(
                co, ("digest", algo, shard_size, pad_rows), flat,
                coalesce.make_digest_kernel(
                    algo, pad_rows, device=self.device_idx), nb,
                lambda: bitrot_io._hash_batch(flat, algo)
            ).reshape(nb, k, bitrot_io.digest_size(algo))
        if self._fused_dev(algo) and not mesh_mode():
            return np.asarray(fused.verify_and_transform(
                y, k, m, tuple(range(k)), (), algo=algo,
                device=self.device_idx)[0])
        return None

    def verify_transform(self, x: np.ndarray, k: int, m: int,
                         sources: tuple, targets: tuple, algo: str,
                         site: str = "get"):
        """Digests (nb, k, hs) of the K chosen rows `x` (nb, k, S) of
        shards `sources`, and shards `targets` rebuilt from them: a
        degraded GET's decode, a heal batch (`site` "heal").  The
        rebuilt rows are T arrays of (nb, S) in `targets` order (None
        where there are no targets), on every plane views of what the
        plane produced: the reader's copy into its own layout is the
        one copy a rebuilt byte gets.  On the lane ONE dispatch of the
        geometry's one decode program a (k, m), built ahead
        (`build_ladder`), shared by concurrent degraded reads and heals
        of one (sources, targets) pattern: digests + reconstruction from
        the same HBM-resident bytes where the chip computes the digest;
        else the digest-free program, one for every algorithm the host
        hashes, while this thread hashes the K rows (`host_digests`)."""
        nb, _, shard_size = x.shape
        DATA_PATH.record_verify_blocks(
            nb, (k, m, sources, targets) if targets else None)
        co = self._co()
        if self._fused_dev(algo) and not mesh_mode():
            def direct():
                digests, rows = fused.verify_and_transform(
                    x, k, m, sources, targets, algo=algo,
                    device=self.device_idx)
                return (np.asarray(digests),
                        tuple(devcache.fetch(r) for r in rows)
                        if targets else None)
            if co is None:
                return direct()
            return self._ride(
                co, ("vt", k, m, sources, targets, algo, shard_size), x,
                self.vt_kernel(k, m, sources, targets, algo,
                               device=self.device_idx), nb, direct)

        # The host hashes (a host-hashed algorithm, no TPU, or an algo
        # whose native host kernel beats its device verify —
        # bitrot_io.device_preferred): submit the rebuild, hash, then
        # wait.  A round whose digests fail throws its rows away.
        def rebuilt():
            out = self.transform(k, m, x, sources, targets)    # (nb, T, S)
            return tuple(out[:, j] for j in range(len(targets)))
        h = None
        if targets and co is not None and self.use_device \
                and not mesh_mode():
            h = co.submit(
                ("vt", k, m, sources, targets, None, shard_size), x,
                self.vt_kernel(k, m, sources, targets, None,
                               device=self.device_idx),
                weight=nb, device=self.device_idx)
        digests = self.host_digests(x.reshape(nb * k, shard_size), algo,
                                    site)
        rows = None
        if h is not None:
            (_, rows), h = _settle(h, lambda: (None, rebuilt()))
            if h is not None:
                h.release()
        elif targets:
            rows = rebuilt()
        return digests.reshape(nb, k, bitrot_io.digest_size(algo)), rows

    @staticmethod
    def host_digests(rows: np.ndarray, algo: str, site: str) -> np.ndarray:
        """Bitrot digests (n, hs) of full shard blocks `rows` (n, S) with
        the host's kernel, on the calling thread: span `engine.hash`,
        counted at `site` (mtpu_host_hash_bytes_total)."""
        with ospan.span("engine.hash") as sp:
            sp.tag(bytes=rows.nbytes)
            out = bitrot_io.hash_rows(rows, algo)
        DATA_PATH.record_host_hash(site, rows.nbytes)
        return out

    def transform(self, k: int, m: int, x, sources, targets,
                  resident=None, algo: str = "") -> np.ndarray:
        """Backend-picking transform: (B, K, S) -> (B, T, S) numpy.
        `resident`: `x` where it already lies on the device
        (ops/devcache.py), dispatched against with zero upload."""
        if mesh_mode():
            out = self._on_mesh(
                k, m, x, 1, lambda sc, b: sc.reconstruct_blocks(
                    b, tuple(sources), tuple(targets)))
            if out is not None:
                return out
        elif resident is not None and self.use_device \
                and algo in fused.DEVICE_ALGOS:
            return fused.rows_on_host(fused.verify_and_transform(
                resident, k, m, tuple(sources), tuple(targets),
                algo=algo, device=self.device_idx)[1])
        if self.use_device:
            return np.asarray(self._codec(k, m).transform_blocks(
                x, tuple(sources), tuple(targets)))
        return np.asarray(self.native(k, m).transform_blocks(
            np.asarray(x), tuple(sources), tuple(targets)))

    def _ride(self, co, key: tuple, payload, kernel, nb: int, direct):
        """Submit to coalescer `co` on this set's lane and wait: the
        result, or `direct()`'s after a failed handle (`_settle`).
        For results that alias nothing pooled (fresh arrays)."""
        out, h = _settle(co.submit(
            key, payload, kernel, weight=nb, device=self.device_idx),
            direct)
        if h is not None:
            h.release()
        return out


class Encoder:
    """The encode of one PUT stream: `encode(blocks)` starts a batch
    ((nb, K, S) uint8) and returns what the caller's one-deep `pending`
    pipeline holds; `frames(pending)` turns it into n framed per-shard
    views.  Start batch i, then frame batch i-1 while the device works:
    dispatch and transfer hide behind host framing and the caller's
    disk writes (the in-flight parallelWriter, cmd/erasure-encode.go:36).
    Coalesced (MTPU_COALESCE), the batch goes to the shared coalescer,
    where concurrent requests' batches stack into ONE launch; its
    future slots into the same `pending`, so in-request overlap stays.

    `overlaps` is False where a batch is one native pass on the calling
    thread (the fused host kernel, direct): `frames` runs it, and the
    caller asks at once.

    On every plane a batch is framed into a buffer its thread already
    holds (`_db_arena`), sized from the batch itself: a 1-block PUT
    touches one block's frames.  The views `frames` returns are valid
    until the buffer comes round again: on the lane, mesh and native
    planes after the next batch but one, always (two alternating
    buffers: batch i is written by the drive pool while batch i+1 is
    framed); the fused host kernel's after the next batch, or with
    `double_buffer` the next but one."""

    def __init__(self, sm: ShardMath, k: int, m: int, algo: str,
                 double_buffer: bool):
        self.sm, self.k, self.m, self.algo = sm, k, m, algo
        self.shard_size = -(-BLOCK_SIZE // k)
        self.frame_len = bitrot_io.digest_size(algo) + self.shard_size
        self.fused_host = sm.host_fused(k, m, algo)
        self.co = sm._co()
        self.overlaps = self.fused_host is None or self.co is not None
        self._alternate = double_buffer or self.fused_host is None
        self._flip = 0
        # Retired coalesced put_frame handles: their results alias a
        # POOLED dispatch buffer, and a pipelined consumer may still be
        # writing batch i when batch i+1 is pulled — so a buffer is
        # only recycled two yields after its batch was handed out.
        # (A device/native encode's are fresh arrays: released at once.)
        self._retired: list = []
        self._keep = 0 if self.fused_host is None else 2

    def encode(self, blocks: np.ndarray) -> tuple:
        """Start a batch: (blocks, coalescer handle or None, what the
        direct pass started — (parity, digests) — or None)."""
        sm, k, m, algo = self.sm, self.k, self.m, self.algo
        nb = blocks.shape[0]
        if self.fused_host is not None:
            DATA_PATH.record_encode_blocks("host", nb)
            if self.co is None:
                return blocks, None, None
            return blocks, self.co.submit(
                ("pf", k, m, self.shard_size), blocks,
                sm._pf_kernel(k, m, self.shard_size), weight=nb,
                device=sm.device_idx), None
        parity = None
        if mesh_mode():
            # Chips outnumber sets (mesh_rule): place the shard
            # matmul on the mesh (blocks x lanes SPMD); digests
            # hash on host.  Mesh placement stays direct — SPMD
            # shapes don't stack across requests.
            parity = sm._on_mesh(
                k, m, blocks, 2, lambda sc, b: sc.encode_blocks(b))
        DATA_PATH.record_encode_blocks(
            "mesh" if parity is not None
            else "lane" if sm.use_device else "host", nb)
        if parity is not None:
            return blocks, None, (parity, None)
        if self.co is None:
            return blocks, None, sm.direct_encode(blocks, k, m, algo)
        fused_dev = sm._fused_dev(algo)
        tag = "fd" if fused_dev else "dev" if sm.use_device else "nat"
        return blocks, self.co.submit(
            ("enc", tag, k, m, algo, self.shard_size), blocks,
            sm.enc_kernel(k, m, algo, fused_dev, device=sm.device_idx),
            weight=nb, device=sm.device_idx), None

    def _out(self, nb: int) -> np.ndarray:
        """The buffer the stream's next batch of `nb` blocks is framed
        into: the thread's, the other one after each where batches
        alternate."""
        slot = self._flip
        if self._alternate:
            self._flip ^= 1
        return _db_arena(slot, (self.k + self.m) * nb * self.frame_len)

    def _direct(self, blocks: np.ndarray):
        """The stream's direct pass: the fused host kernel's frames,
        else `direct_encode`'s pair."""
        k, m = self.k, self.m
        if self.fused_host is None:
            return self.sm.direct_encode(blocks, k, m, self.algo)
        per = blocks.shape[0] * self.frame_len
        a = self._out(blocks.shape[0])
        return self.fused_host.put_frame(
            blocks, k, m,
            outs=[a[i * per:(i + 1) * per] for i in range(k + m)])

    def frames(self, p: tuple) -> list:
        """n framed shard-chunks of a batch `encode` started."""
        blocks, h, out = p
        if h is not None:
            out, h = _settle(h, lambda: self._direct(blocks))
            if h is not None:
                self._retired.append(h)
                while len(self._retired) > self._keep:
                    self._retired.pop(0).release()
        elif out is None:
            out = self._direct(blocks)
        if self.fused_host is not None or out[1] is None:
            DATA_PATH.record_host_hash(
                "put", (self.k + self.m) * blocks.shape[0] * self.shard_size)
        if self.fused_host is not None:
            return out
        parity, digests = out
        # np.asarray here is the device sync point; by the time we
        # take it, the NEXT batch's dispatch is already in flight.
        # frame_shard_views fills the framed layout in one pass, into
        # the stream's buffer, and returns zero-copy per-shard views.
        if digests is not None:
            digests = np.asarray(digests)
        parity = np.asarray(parity)
        with ospan.span("engine.frame"):
            return bitrot_io.frame_shard_views(
                blocks, parity, digests, self.algo,
                out=self._out(blocks.shape[0]))
