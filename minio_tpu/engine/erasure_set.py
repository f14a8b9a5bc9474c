"""One erasure set: quorum CRUD over a stripe of N drives.

The erasureObjects equivalent (/root/reference/cmd/erasure-object.go:748) with
the streaming encode/decode drivers (/root/reference/cmd/erasure-encode.go:36,
cmd/erasure-decode.go:101) redesigned TPU-first:

- data is staged in batches of 1 MiB blocks and erasure-coded one batch
  ((B, K, S) uint8) a dispatch instead of the reference's per-block
  synchronous SIMD calls (SURVEY.md §5); which plane computes a batch,
  coalesced or direct, is `self.math`'s to say (engine/shardmath.py);
- shard fan-out to drives runs on a thread pool with write-quorum reduce
  (the parallelWriter analogue);
- reads fetch exactly K shards, verify bitrot frames, trigger spare reads
  on failure (the parallelReader analogue), and reconstruct missing rows
  through the same seam;
- small objects (<= 128 KiB) inline their framed shards into xl.meta and
  bypass the device (SURVEY.md §7 hard-part #2).
"""

from __future__ import annotations

import hashlib
import os
import queue as _queuemod
import threading
import time
import uuid
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..cluster.dynamic_timeout import DynamicTimeout
from ..observe import span as ospan
from ..observe.metrics import DATA_PATH
from ..ops import devcache as devcache_mod
from ..ops import metalanes
from ..ops import zerocopy as zc
from ..parallel import pipeline as pl
from ..storage import bitrot_io
from ..storage.drive import (SMALL_FILE_THRESHOLD, SYS_VOL, TMP_DIR,
                             LocalDrive, read_rows, rows_readable)
from ..storage.errors import (ErrBucketExists, ErrBucketNotFound,
                              ErrDiskNotFound, ErrErasureReadQuorum,
                              ErrErasureWriteQuorum, ErrFileCorrupt,
                              ErrFileNotFound, ErrFileVersionNotFound,
                              ErrObjectNotFound, ErrVersionNotFound,
                              ErrVolumeExists, ErrVolumeNotFound,
                              StorageError)
from ..storage.health_wrap import drive_available
from ..storage.xlmeta import (ErasureInfo, FileInfo, ObjectPartInfo, XLMeta,
                              new_uuid, normalize_version_id)
from ..utils import streams
from ..utils.crashpoints import crash_point
from . import quorum as Q
from . import segarena
from .shardmath import BATCH_BLOCKS, BLOCK_SIZE, ShardMath
# ops/ipc_dispatch.py (the pool owner's "pf" kernel) imports the fused host
# kernel's loader from here under this name (ROADMAP Design 1: ops asking up).
from .shardmath import ecio_mod as _ecio_mod  # noqa: F401

def _get_fastpath() -> bool:
    """Healthy-read verify-only fast path gate (MTPU_GET_FASTPATH).

    Default on: when all k data shards are present, `_read_part`
    dispatches a batched verify-only bitrot check and assembles the
    object from systematic shard slices with zero GF(2^8) work.
    MTPU_GET_FASTPATH=0 forces the fused verify+decode path — the
    oracle the equivalence tests diff against (read per call so tests
    can flip it without re-importing)."""
    return os.environ.get("MTPU_GET_FASTPATH", "1") != "0"


#: Drive-pool thread tag (see ErasureSet.__init__): lets fan-out helpers
#: detect they are ALREADY on this set's drive pool and run inline
#: instead of nested-submitting — a task queued behind its own parent is
#: the one thread-pool deadlock shape this engine can produce.
def _hedge_enabled() -> bool:
    """Hedged shard-read gate (MTPU_HEDGE, default on).

    The Tail-at-Scale move: when a stripe read's stragglers outlive an
    adaptive delay, speculatively read parity spares and take whichever
    k distinct shards answer first — erasure coding makes the hedge
    nearly free since any k of k+m reconstruct.  MTPU_HEDGE=0 is the
    wait-for-your-shard oracle (read per call so tests flip it live)."""
    return os.environ.get("MTPU_HEDGE", "1") != "0"


def _hedge_fixed_ms() -> float | None:
    """MTPU_HEDGE_MS pins the hedge delay (tests/benchmarks); unset
    means the per-set DynamicTimeout adapts it from observed reads."""
    v = os.environ.get("MTPU_HEDGE_MS", "")
    try:
        return float(v) if v else None
    except ValueError:
        return None


_POOL_LOCAL = threading.local()


def _tag_pool_thread(tag: str) -> None:
    _POOL_LOCAL.tag = tag


def _now_ns() -> int:
    return time.time_ns()


def _join_range(blocks, tail, lo: int, length: int, dst=None):
    """Bytes [lo, lo + length) of the rows of `blocks` ((nb, W) uint8,
    or None) followed by `tail` (1-D, or None).

    Where one contiguous piece IS that range and no `dst` was given, a
    memoryview of it and nothing is copied.  Else one strided copy (in
    numpy, so without the GIL) into `dst` (a writable buffer of at least
    `length` bytes; None is returned) or into a leased arena, returned
    as a memoryview.  Either view keeps its arena out of the segment
    pool while anything holds it (engine/segarena.py), so what is handed
    out is never written again.  Returns (result, bytes copied)."""
    if length <= 0:
        return (None if dst is not None else b""), 0
    if dst is None and lo == 0 and (blocks is None or tail is None):
        piece = tail if blocks is None else blocks
        if piece.size == length and piece.flags.c_contiguous:
            return memoryview(piece.reshape(-1)), 0
    out = (np.frombuffer(dst, dtype=np.uint8)[:length] if dst is not None
           else segarena.lease(length, "join"))
    pos = 0
    if blocks is not None:
        nb, w = blocks.shape
        r, c = divmod(lo, w)
        if c and r < nb:                # the range starts inside a row
            pos = min(w - c, length)
            out[:pos] = blocks[r, c:c + pos]
            r += 1
        n = min(nb - r, (length - pos) // w)
        if n > 0:                       # whole rows: one strided copy
            out[pos:pos + n * w].reshape(n, w)[:] = blocks[r:r + n]
            pos += n * w
            r += n
        if r < nb and pos < length:     # ... and ends inside one
            out[pos:] = blocks[r, :length - pos]
            pos = length
        lo = max(lo - nb * w, 0)
    if pos < length:
        out[pos:] = tail[lo:lo + length - pos]
    return (None if dst is not None else memoryview(out)), length


class ErasureSet:
    """Object CRUD on one stripe of `n` drives (entries may be None when a
    drive is offline)."""

    def __init__(self, drives: list[LocalDrive | None],
                 default_parity: int | None = None,
                 set_index: int = 0, nslock=None):
        self.drives = list(drives)
        self.n = len(drives)
        if self.n < 2:
            raise ValueError("an erasure set needs >= 2 drives")
        self.default_parity = (self.n // 2 if default_parity is None
                               else default_parity)
        self.set_index = set_index
        # The set's shard math, and where it runs (engine/shardmath.py).
        self.math = ShardMath(set_index)
        # Pool-nesting invariant: work running ON self.pool must never
        # block on another self.pool future.  Two mechanisms enforce it:
        # (1) layered executors — prefetch tasks (get_object_iter
        # segments) WAIT on self.pool leaf tasks, so they get their own
        # _iter_pool; coalesced-dispatch futures resolve on the
        # coalescer's dedicated thread, never this pool; and (2) the
        # initializer tags every pool thread so fan-out helpers
        # (_map_drives, _map_drives_positions, _hash_shard_frames, the
        # read-shard fan-outs) detect re-entry and run inline instead
        # of nested-submitting behind their own parent task.
        self._pool_tag = f"drive-pool-{set_index}-{id(self)}"
        self.pool = ThreadPoolExecutor(max_workers=max(self.n, 4),
                                       initializer=_tag_pool_thread,
                                       initargs=(self._pool_tag,))
        self._iter_pool = ThreadPoolExecutor(max_workers=8)
        # Namespace locks guard object mutations (cf. NSLock use at
        # cmd/erasure-object.go:930). Standalone default: in-process RW
        # locks; a distributed deployment injects an NSLockMap over the
        # set's (local+remote) lockers (cluster/nslock.py).
        if nslock is None:
            from ..cluster.nslock import NSLockMap
            nslock = NSLockMap()
        self.nslock = nslock
        # Optional background-subsystem hooks: an MRF queue receives
        # partial-write failures; the dirty tracker feeds the scanner's
        # changed-bucket skip logic (background/usage.py).
        self.mrf = None
        self._dirty_tracker = None
        self._bucket_cache: dict[str, float] = {}
        # Parsed-quorum FileInfo cache for the GET fan-out: a ranged GET
        # split into N segment requests must not re-read and re-elect
        # xl.meta N times.  Entries are (bucket generation, stamp, fi,
        # metas, errs); any write path bumps the bucket's generation via
        # _mark_dirty, and a short TTL bounds cross-process staleness
        # exactly like the bucket-existence cache above.
        self._fi_cache: dict[tuple, tuple] = {}
        self._fi_gen: dict[str, int] = {}
        # Optional RAM hot-object tier (engine/hotcache.py): attached
        # by attach_pools/attach_sets only when every drive is local.
        # Invalidation piggybacks on _mark_dirty — same generation
        # discipline as the FileInfo cache, but in shared memory so a
        # pool sibling's PUT invalidates this process's hits too.
        self.hot_tier = None
        # Hedged-read state: the hedge delay adapts like a lock deadline
        # (log_timeout when the timer fires, log_success when the
        # slowest needed shard beat it), and per-drive-position read
        # EWMAs let the 1-core serial host decide when fanning out is
        # worth the thread hops (a known-slow drive) vs. pure overhead
        # (every drive fast).  Lock-free float updates: a lost race
        # skews a hint, nothing more.
        self._hedge_dyn = DynamicTimeout(0.05, 0.002, 2.0)
        self._read_ewma_ms = [0.0] * self.n
        # Device-resident shard cache identity (ops/devcache.py): a
        # fresh per-process owner token per ErasureSet instance, so a
        # reopened set (crash recovery, decom re-attach) can never see
        # entries filled by a previous incarnation.
        self._devcache_owner = devcache_mod.next_owner()
        from .metacache import Metacache
        self.metacache = Metacache(self)

    #: FileInfo-cache tuning: TTL matches the bucket-existence cache
    #: window; the size cap only matters for pathological key churn
    #: (clearing wholesale is fine — it is a latency cache, not state).
    _FI_CACHE_TTL = 2.0
    _FI_CACHE_MAX = 512

    def _mark_dirty(self, bucket: str) -> None:
        if self._dirty_tracker is not None:
            self._dirty_tracker.mark(bucket)
        self._fi_gen[bucket] = self._fi_gen.get(bucket, 0) + 1
        self.metacache.bump(bucket)
        if self.hot_tier is not None:
            self.hot_tier.note_mutation(bucket)
        # Always recorded, even with MTPU_DEVCACHE=0 — a mutation made
        # while the cache is disabled must still invalidate entries a
        # later re-enable would otherwise resurrect.
        devcache_mod.get().note_mutation(self._devcache_owner, bucket)

    # -- codec helpers -------------------------------------------------------

    @property
    def device_idx(self) -> int:
        """The coalescer-lane device this set's kernel traffic rides
        (`ShardMath.device_idx`: `set_index % n_devices`, per call)."""
        return self.math.device_idx

    # -- drive fan-out helpers ----------------------------------------------

    def _map_drives(self, fn, drives=None, inline: bool = False) -> list:
        """Run fn(drive) on every drive in parallel; exceptions captured.
        `inline` runs the calls one after another on the calling thread
        instead (the xl.meta read fan-out over in-process drives).

        Returns list of (result, error) per drive position.
        """
        drives = self.drives if drives is None else drives

        def call(d):
            if d is None:
                return None, ErrDiskNotFound("offline")
            try:
                return fn(d), None
            except Exception as e:  # noqa: BLE001 — quorum layer classifies
                return None, e

        if inline or self._serial_local(drives) or self._on_drive_pool():
            return [call(d) for d in drives]
        # wrap_ctx: per-drive spans born in pool threads still attach
        # to the traced request (no-op when untraced).
        return list(self.pool.map(ospan.wrap_ctx(call), drives))

    # -- bucket ops ----------------------------------------------------------

    def make_bucket(self, bucket: str) -> None:
        res = self._map_drives(lambda d: d.make_volume(bucket))
        errs = [e for _, e in res]
        # Already present on every drive -> the bucket truly exists.
        if errs and all(isinstance(e, ErrVolumeExists) for e in errs):
            raise ErrBucketExists(bucket)
        # Partial existence is the heal case: treat as success.
        errs = [None if isinstance(e, ErrVolumeExists) else e for e in errs]
        err = Q.reduce_write_quorum_errs(errs, self.n // 2 + 1)
        if err is not None:
            raise err

    def bucket_exists(self, bucket: str, cached: bool = False) -> bool:
        # cached=True serves the WRITE hot path's pre-check (put_object
        # probes existence on every call): a stale positive there is
        # backstopped by the per-drive ErrVolumeNotFound the write
        # itself surfaces. Reads and explicit existence queries
        # (HeadBucket, error classification) always stat — a cluster
        # peer's delete must be visible immediately, not after a TTL.
        now = time.monotonic()
        if cached:
            hit = self._bucket_cache.get(bucket)
            if hit is not None and now - hit < 2.0:
                return True
        res = self._map_drives(lambda d: d.stat_volume(bucket))
        ok = sum(1 for _, e in res if e is None)
        exists = ok >= self._live_quorum()
        if exists:
            self._bucket_cache[bucket] = now
        else:
            self._bucket_cache.pop(bucket, None)
        return exists

    def delete_bucket(self, bucket: str, force: bool = False) -> None:
        self._bucket_cache.pop(bucket, None)
        res = self._map_drives(lambda d: d.delete_volume(bucket, force=force))
        errs = [e for _, e in res]
        if errs and all(isinstance(e, ErrVolumeNotFound) for e in errs):
            raise ErrBucketNotFound(bucket)
        errs = [None if isinstance(e, ErrVolumeNotFound) else e for e in errs]
        err = Q.reduce_write_quorum_errs(errs, self.n // 2 + 1)
        if err is not None:
            raise err
        # Recreating the bucket must not resurrect pre-delete cache
        # entries (FileInfo cache or hot tier).
        self._mark_dirty(bucket)

    def list_buckets(self) -> list[str]:
        res = self._map_drives(lambda d: d.list_volumes())
        counts: dict[str, int] = {}
        for vols, e in res:
            if e is None:
                for v in vols:
                    counts[v] = counts.get(v, 0) + 1
        quorum = self._live_quorum()
        return sorted(v for v, c in counts.items() if c >= quorum)

    def _live_quorum(self) -> int:
        live = sum(1 for d in self.drives if d is not None)
        return max(1, live // 2)

    # -- put -----------------------------------------------------------------

    def put_object(self, bucket: str, obj: str, data, *,
                   metadata: dict | None = None,
                   versioned: bool = False,
                   parity: int | None = None,
                   version_id: str | None = None,
                   mod_time_ns: int | None = None) -> FileInfo:
        """Erasure-code and store one object (single part).

        `data` is bytes or a reader (.read(n)); a reader streams through
        encode in O(BATCH_BLOCKS x BLOCK_SIZE) memory — the role of the
        reference's blockwise streaming Encode
        (/root/reference/cmd/erasure-encode.go:73).

        `version_id`/`mod_time_ns` override the generated identity —
        the decommission mover re-PUTs a drained pool's versions through
        this path and must preserve each version's id and timestamp or
        the moved history would reorder (a moved OLD version would
        eclipse a client write that raced the drain).

        cf. erasureObjects.putObject, /root/reference/cmd/erasure-object.go:748.
        """
        with ospan.span("engine.bucket_check"):
            if not self.bucket_exists(bucket, cached=True):
                raise ErrBucketNotFound(bucket)
        with self.nslock.write_locked(bucket, obj):
            fi = self._put_object_locked(bucket, obj, data,
                                         metadata=metadata,
                                         versioned=versioned,
                                         parity=parity,
                                         version_id=version_id,
                                         mod_time_ns=mod_time_ns)
        self._mark_dirty(bucket)
        return fi

    def clamp_parity(self, parity: int | None) -> int:
        """Request-supplied parity (storage-class plumbing) clamped to
        the stripe's sane range — EC:N beyond n/2 would starve data
        shards (the reference validates SC parity the same way,
        internal/config/storageclass/storage-class.go)."""
        if parity is None:
            return self.default_parity
        return max(0, min(int(parity), self.n // 2))

    def _put_object_locked(self, bucket, obj, data, *, metadata,
                           versioned, parity, version_id=None,
                           mod_time_ns=None) -> FileInfo:
        parity = self.clamp_parity(parity)
        # Parity upgrade: offline drives become parity so the write keeps
        # full reconstruction capability (cf. erasure-object.go:766-800).
        # Breaker-OFFLINE drives count too — their writes fail fast, so
        # the stripe needs the same extra parity as a physical hole.
        offline = sum(1 for d in self.drives if not drive_available(d))
        upgraded = False
        if offline and parity < self.n // 2:
            parity = min(parity + offline, self.n // 2)
            upgraded = True
        k = self.n - parity
        write_quorum = k + (1 if k == parity else 0)

        # A streamed body: peek enough to decide inline-vs-streaming;
        # small bodies collapse to the bytes path.
        stream = None
        if streams.is_reader(data):
            stream = data
            # Loop: a reader may legally return short reads before EOF.
            head = bytearray()
            while len(head) <= SMALL_FILE_THRESHOLD:
                piece = stream.read(SMALL_FILE_THRESHOLD + 1 - len(head))
                if not piece:
                    break
                head += piece
            head = bytes(head)
            if len(head) <= SMALL_FILE_THRESHOLD:
                data, stream = head, None
            else:
                data = head

        distribution = Q.hash_order(f"{bucket}/{obj}", self.n)
        meta = dict(metadata or {})
        # Overlap the MD5 etag with encode+write: the body is queued to
        # a digest worker in 1 MiB views and hashed WHILE the shard
        # pipeline encodes/writes (hashlib, the codec kernels, and file
        # IO all release the GIL, so the overlap is real even on the
        # 1-core host, where the up-front digest was the measured PUT
        # wall).  Resolved before publish; byte-identical ETags.
        etag_md5 = None
        if stream is None and "etag" not in meta:
            etag_md5 = streams.PipelinedMD5()
            etag_md5.feed(data)
        if upgraded:
            meta["x-mtpu-internal-erasure-upgraded"] = f"{offline}-offline"
        if version_id is None:
            version_id = new_uuid() if versioned else ""
        mod_time = mod_time_ns if mod_time_ns is not None else _now_ns()
        if mod_time_ns is not None:
            # A preserved-timestamp write (the decommission mover) must
            # never clobber a NEWER racing client write: the mover's
            # copy of a drained version is stale the instant a client
            # overwrites or deletes the object mid-drain, and last-
            # write-wins on the xl.meta slot would silently resurrect
            # the old bytes.  Under the namespace write lock the check
            # is race-free.
            try:
                cur = self._read_metadata(bucket, obj, version_id)[0]
                if cur.mod_time_ns >= mod_time:
                    return cur
            except StorageError:
                pass

        algo = bitrot_io.write_algo()
        ec_base = ErasureInfo(
            data_blocks=k, parity_blocks=parity, block_size=BLOCK_SIZE,
            index=0, distribution=distribution,
            checksums=[{"part": 1, "algo": algo, "hash": b""}])
        # Object size: known up front for bytes, discovered at EOF for a
        # stream — fi_for reads it at publish time (after the stream).
        sizeref = {"size": len(data) if stream is None else None}

        def fi_for(drive_pos: int, data_dir: str,
                   inline: bytes | None) -> FileInfo:
            size = sizeref["size"]
            ec = ErasureInfo(
                data_blocks=k, parity_blocks=parity, block_size=BLOCK_SIZE,
                index=distribution[drive_pos], distribution=distribution,
                checksums=ec_base.checksums)
            return FileInfo(
                volume=bucket, name=obj, version_id=version_id,
                data_dir=data_dir, mod_time_ns=mod_time, size=size,
                metadata=meta,
                parts=[ObjectPartInfo(1, size, size)],
                erasure=ec, inline_data=inline)

        if stream is None and len(data) <= SMALL_FILE_THRESHOLD:
            if etag_md5 is not None:
                with ospan.span("engine.etag"):
                    meta.setdefault("etag", etag_md5.hexdigest())
            return self._put_inline(bucket, obj, data, fi_for, k, parity,
                                    distribution, write_quorum, algo)

        # Streaming path: encode batches of blocks on device, append framed
        # shards to per-drive staging files, publish with rename_data.
        data_dir = new_uuid()
        tmp_id = f"put-{uuid.uuid4().hex}"
        failed = [d is None for d in self.drives]

        # Streamed bodies pipeline their digest too: each pulled chunk
        # is queued to the digest worker and hashes under the NEXT
        # chunk's read+encode instead of serially before it.
        md5 = streams.PipelinedMD5() if stream is not None \
            else hashlib.md5()
        total = 0

        def counted_chunks():
            nonlocal total
            for chunk, is_last in streams.batched_chunks(
                    data, stream, BATCH_BLOCKS * BLOCK_SIZE,
                    digest=md5 if stream is not None else None):
                if stream is not None:
                    with ospan.span("engine.etag"):
                        md5.update(chunk)  # bytes path already has its etag
                total += len(chunk)
                yield chunk, is_last

        # Fast path: the whole object fits in one encode dispatch
        # (bytes body <= one batch). Encode, then ONE fan-out per
        # drive doing write+publish together — the generic path costs
        # two thread-pool round-trips per batch plus an all-drive
        # cleanup sweep, which dominates small-object latency (the
        # parallelWriter+RenameData pair in the reference is likewise
        # one connection round per drive, cmd/erasure-object.go:1200).
        if stream is None and len(data) <= BATCH_BLOCKS * BLOCK_SIZE:
            try:
                with ospan.span("engine.encode"):
                    batches = list(self._encode_chunks(
                        [(data, True)], k, parity, algo))
            finally:
                if etag_md5 is not None:
                    etag_md5.close()     # worker drains what's queued
            if etag_md5 is not None:
                with ospan.span("engine.etag"):
                    meta.setdefault("etag", etag_md5.hexdigest())
            per_drive = [Q.unshuffle_to_drives(b, distribution)
                         for b in batches]

            def stage(pos):
                d = self.drives[pos]
                if d is None:
                    raise ErrDiskNotFound("offline")
                bufs = [pdc[pos] for pdc in per_drive]
                # Vectored staging: the whole per-drive fan-out is one
                # open + fallocate + pwritev instead of one
                # open/write/close per batch.  Feature-detected so
                # RPC/remote drives (no write_file_batches) keep the
                # append loop; MTPU_ZEROCOPY=0 is the oracle.
                wfb = (getattr(d, "write_file_batches", None)
                       if zc.zerocopy_enabled() else None)
                if wfb is not None:
                    wfb(SYS_VOL, f"{TMP_DIR}/{tmp_id}/part.1", bufs)
                    return
                for buf in bufs:
                    d.append_file(SYS_VOL,
                                  f"{TMP_DIR}/{tmp_id}/part.1", buf)

            # Quorum gate BETWEEN staging and publish: nothing becomes
            # visible unless enough drives staged — a failed PUT must
            # not leave committed versions on the survivors (the
            # reference likewise aborts before RenameData,
            # cmd/erasure-object.go:1200).
            with ospan.span("engine.write"):
                res = self._map_drives_positions(stage)
            stage_errs = [e for _, e in res]
            err = Q.reduce_write_quorum_errs(stage_errs, write_quorum)
            if err is not None:
                self._cleanup_tmp(tmp_id)
                raise err

            def publish(pos):
                if stage_errs[pos] is not None:
                    raise ErrDiskNotFound("stage failed")
                self.drives[pos].rename_data(
                    SYS_VOL, f"{TMP_DIR}/{tmp_id}",
                    fi_for(pos, data_dir, None), bucket, obj)

            with ospan.span("engine.publish"):
                res = self._map_drives_positions(publish)
            errs = [e for _, e in res]
            err = Q.reduce_write_quorum_errs(errs, write_quorum)
            if err is not None:
                self._undo_publish(bucket, obj,
                                   fi_for(0, data_dir, None), errs)
                self._cleanup_tmp(tmp_id)
                raise err
            crash_point("put.post_publish")
            if any(errs):
                # Only failed drives can still hold staging files —
                # successful publishes renamed theirs away.
                self._cleanup_tmp(tmp_id)
            fi = fi_for(0, data_dir, None)
            if self.mrf is not None and any(errs):
                self.mrf.enqueue(bucket, obj, fi.version_id)
            return fi

        # try/finally: a reader that raises mid-stream (client
        # disconnect, truncated body, hash mismatch at EOF) must not
        # leak per-drive staging files — they only get swept again at
        # drive startup.
        try:
            for batch_shards in ospan.timed_iter(
                    self._encode_chunks(counted_chunks(), k, parity, algo),
                    "engine.encode"):
                # batch_shards: n framed byte strings in SHARD order.
                with ospan.span("engine.shuffle"):
                    per_drive = Q.unshuffle_to_drives(batch_shards,
                                                      distribution)

                def write_one(pos):
                    d = self.drives[pos]
                    if d is None or failed[pos]:
                        return
                    # Streaming batches ride the vectored writer too (a
                    # one-element iovec): same single open per batch,
                    # but with fallocate extension and the
                    # O_DIRECT-when-aligned path for bulk shards.
                    wfb = (getattr(d, "write_file_batches", None)
                           if zc.zerocopy_enabled() else None)
                    if wfb is not None:
                        wfb(SYS_VOL, f"{TMP_DIR}/{tmp_id}/part.1",
                            [per_drive[pos]])
                        return
                    d.append_file(SYS_VOL, f"{TMP_DIR}/{tmp_id}/part.1",
                                  per_drive[pos])

                with ospan.span("engine.write"):
                    res = self._map_drives_positions(write_one)
                for pos, (_, e) in enumerate(res):
                    if e is not None:
                        failed[pos] = True
                if sum(1 for f in failed if not f) < write_quorum:
                    raise ErrErasureWriteQuorum(
                        f"{self.n - sum(failed)} < {write_quorum}")

            if stream is not None:
                sizeref["size"] = total
                with ospan.span("engine.etag"):
                    meta.setdefault("etag", md5.hexdigest())
            elif etag_md5 is not None:
                with ospan.span("engine.etag"):
                    meta.setdefault("etag", etag_md5.hexdigest())

            def publish(pos):
                d = self.drives[pos]
                if d is None or failed[pos]:
                    raise ErrDiskNotFound("offline/failed")
                d.rename_data(SYS_VOL, f"{TMP_DIR}/{tmp_id}",
                              fi_for(pos, data_dir, None), bucket, obj)

            with ospan.span("engine.publish"):
                res = self._map_drives_positions(publish)
            errs = [e for _, e in res]
            err = Q.reduce_write_quorum_errs(errs, write_quorum)
            if err is not None:
                self._undo_publish(bucket, obj,
                                   fi_for(0, data_dir, None), errs)
                raise err
            crash_point("put.post_publish")
        finally:
            # Always sweep staging: publish renames the winners away;
            # failed/partial drives still hold tmp shard files.  The
            # digest workers must be released too — an abandoned one
            # would hold its slot until the idle backstop.
            if etag_md5 is not None:
                etag_md5.close()
            if isinstance(md5, streams.PipelinedMD5):
                md5.close()
            self._cleanup_tmp(tmp_id)
        fi = fi_for(0, data_dir, None)
        # Partial success (quorum met, some drives failed): queue for MRF
        # heal so the stripe returns to full width without waiting for
        # the scanner (cf. enqueue at cmd/erasure-object.go:1403).
        if self.mrf is not None and (any(failed) or any(errs)):
            self.mrf.enqueue(bucket, obj, fi.version_id)
        return fi

    def _put_inline(self, bucket, obj, data, fi_for, k, parity,
                    distribution, write_quorum, algo: str) -> FileInfo:
        """Small objects: framed shards live inline in each drive's xl.meta
        (cf. inline data, /root/reference/cmd/xl-storage.go:1183)."""
        with ospan.span("engine.encode"):
            shards = self._encode_full(data, k, parity, algo)  # n framed
        per_drive = Q.unshuffle_to_drives(shards, distribution)

        def write_one(pos):
            d = self.drives[pos]
            if d is None:
                raise ErrDiskNotFound("offline")
            d.write_metadata(bucket, obj, fi_for(pos, "", per_drive[pos]))

        # Publish routing: a lone request takes the exact solo fan-out
        # (one fsynced write_metadata per drive — oracle latency and
        # oracle durability mechanics); once the request-level inflight
        # counter or a busy lane proves concurrency, publishes route
        # through the per-drive metadata lanes where same-drive
        # batch-mates share ONE journal fsync (group commit).
        use_lanes = False
        mb = None
        if metalanes.enabled():
            mb = metalanes.get()
            mb.note_put(1)
            use_lanes = mb.put_hot() or metalanes.solo_forced()
        try:
            with ospan.span("engine.write"):
                if use_lanes:
                    res = self._put_inline_lanes(
                        bucket, obj, fi_for, per_drive, mb)
                else:
                    res = self._map_drives_positions(write_one)
        finally:
            if mb is not None:
                mb.note_put(-1)
        errs = [e for _, e in res]
        err = Q.reduce_write_quorum_errs(errs, write_quorum)
        if err is not None:
            self._undo_publish(bucket, obj, fi_for(0, "", None), errs)
            raise err
        crash_point("put.inline.post_meta")
        fi = fi_for(0, "", None)
        if self.mrf is not None and any(errs):
            # Same partial-success rule as the streaming path.
            self.mrf.enqueue(bucket, obj, fi.version_id)
        return fi

    def _put_inline_lanes(self, bucket, obj, fi_for, per_drive,
                          mb) -> list:
        """Submit one xl.meta publish per position to its drive's
        write lane and collect the handles into the same
        ``[(result, error)]`` shape `_map_drives_positions` returns.
        Submission never touches the drive pool (the lanes own their
        dispatcher threads), so this path composes with nested
        fan-outs without deadlock."""
        handles: list = []
        for pos in range(self.n):
            d = self.drives[pos]
            if d is None:
                handles.append(None)
                continue
            try:
                handles.append(mb.submit_write(
                    d, bucket, obj, fi_for(pos, "", per_drive[pos])))
            except Exception as e:  # noqa: BLE001 — quorum classifies
                handles.append(e)
        out = []
        for h in handles:
            if h is None:
                out.append((None, ErrDiskNotFound("offline")))
            elif isinstance(h, Exception):
                out.append((None, h))
            else:
                try:
                    out.append((h.result(), None))
                except Exception as e:  # noqa: BLE001 — quorum classifies
                    out.append((None, e))
        return out

    #: One-core hosts (this bench VM) gain nothing from a thread pool —
    #: the per-drive work is GIL-bound glue plus page-cache writes, and
    #: pool coordination costs ~0.5 ms/call. Multi-core hosts keep the
    #: parallel fan-out (real deployments: one thread per drive, like
    #: the reference's per-disk goroutines). Remote drives always fan
    #: out — network round-trips overlap even with one core.
    _SERIAL_FANOUT = (os.cpu_count() or 2) == 1

    def _in_process(self, drives=None) -> bool:
        """Every drive is a LocalDrive of this process (or a hole);
        isinstance sees through HealthWrappedDrive."""
        return all(
            isinstance(d, (LocalDrive, type(None)))
            for d in (self.drives if drives is None else drives))

    def _serial_local(self, drives=None) -> bool:
        """One policy, three dispatch sites: serial per-drive calls
        only on a 1-core host whose drives are all in-process."""
        return self._SERIAL_FANOUT and self._in_process(drives)

    def _on_drive_pool(self) -> bool:
        """True when the calling thread IS one of this set's drive-pool
        workers: a nested fan-out must run inline — submitting to the
        pool it occupies and blocking on the result can deadlock once
        every worker does the same (the hazard the prefetch _iter_pool
        comment in __init__ guards the iterator path against)."""
        return getattr(_POOL_LOCAL, "tag", None) == self._pool_tag

    # -- hedged shard reads --------------------------------------------------

    def _note_read_ms(self, pos: int, ms: float) -> None:
        cur = self._read_ewma_ms[pos]
        self._read_ewma_ms[pos] = ms if cur == 0.0 else 0.25 * ms + 0.75 * cur

    def _hedge_delay_s(self) -> float:
        fixed = _hedge_fixed_ms()
        if fixed is not None:
            return fixed / 1e3
        return self._hedge_dyn.timeout()

    def _hedge_worthwhile(self, positions: list[int]) -> bool:
        """Serial-host hedge ignition: fanning k reads across threads
        costs real milliseconds on a 1-core box, so only do it when the
        per-position EWMAs actually show a straggler — one position
        markedly slower than the fastest known (or >5 ms absolute)."""
        known = [self._read_ewma_ms[p] for p in positions
                 if self._read_ewma_ms[p] > 0.0]
        if not known:
            return False
        return max(known) > max(5.0, 4.0 * min(known))

    def _hedged_fetch(self, read_shard, order, rows, tried, want,
                      spares, k: int) -> set[int]:
        """First-k-wins gather.  Launch `want` shard reads concurrently;
        if stragglers outlive the adaptive hedge delay, launch parity
        `spares` to cover them; a FAILED read promotes a spare
        immediately (no timer).  Fills `rows` until k distinct shards
        answered (or everything failed) and returns the shard indices
        still in flight — abandoned losers whose results are ignored.
        The caller must un-`tried` those so a later retry round may
        re-read them.  Slow drives need no explicit demerit here: their
        in-flight wrapper call is still timing, so the breaker's latency
        ledger sees every straggle.
        """
        q: _queuemod.Queue = _queuemod.Queue()
        inflight: set[int] = set()

        def launch(s):
            tried.add(s)
            inflight.add(s)
            pos = order[s]

            def run():
                try:
                    q.put((s, read_shard(pos), None))
                except BaseException as e:  # noqa: BLE001 — marshalled
                    q.put((s, None, e))
            self.pool.submit(ospan.wrap_ctx(run))

        for s in want:
            launch(s)
        spares = list(spares)
        t0 = time.monotonic()
        deadline = t0 + self._hedge_delay_s()
        fired = False
        hedged: set[int] = set()
        n_spares = wins = 0
        while len(rows) < k and inflight:
            if not fired and spares:
                left = deadline - time.monotonic()
                if left <= 0:
                    # Timer: cover every straggler with a spare at once
                    # (k-len(rows) are missing; that many spares close
                    # the read if every straggler is truly stuck).
                    for _ in range(min(len(spares), k - len(rows))):
                        s = spares.pop(0)
                        hedged.add(s)
                        launch(s)
                        n_spares += 1
                    fired = True
                    self._hedge_dyn.log_timeout()
                    continue
                try:
                    item = q.get(timeout=left)
                except _queuemod.Empty:
                    continue
            else:
                # Every launched read puts exactly one item — blocking
                # without a timeout cannot hang while inflight is
                # non-empty.
                item = q.get()
            s, r, err = item
            inflight.discard(s)
            if err is None:
                rows[s] = r
                if s in hedged:
                    wins += 1
            elif spares:
                sp = spares.pop(0)
                launch(sp)
                n_spares += 1
        if not fired:
            self._hedge_dyn.log_success(time.monotonic() - t0)
        DATA_PATH.record_hedge(fired=fired, spares=n_spares, wins=wins)
        return inflight

    def _map_drives_positions(self, fn, parallel: bool = False) -> list:
        """Like _map_drives but fn gets the drive *position*.

        ``parallel=True`` forces the pool fan-out even on the 1-core
        host — for syscall-heavy per-drive work (multipart complete's
        publish: per-part stat + meta read + renames) where the GIL is
        released in the kernel and overlap beats pool overhead."""
        if (not parallel and self._serial_local()) \
                or self._on_drive_pool():
            out = []
            for pos in range(self.n):
                try:
                    out.append((fn(pos), None))
                except Exception as e:  # noqa: BLE001
                    out.append((None, e))
            return out

        def call(pos):
            try:
                return fn(pos), None
            except Exception as e:  # noqa: BLE001
                return None, e
        return list(self.pool.map(ospan.wrap_ctx(call), range(self.n)))

    # -- encode drivers ------------------------------------------------------

    def _encode_full(self, data: bytes, k: int, m: int,
                     algo: str) -> list[bytes]:
        """Encode a small object in one shot; returns n framed shard files."""
        out = [bytearray() for _ in range(k + m)]
        for framed in self._encode_stream(data, k, m, algo):
            for i, b in enumerate(framed):
                # Frames arrive as ndarray views (fused kernel) or bytes
                # (CPU tail); bytearray += needs a buffer, not an array.
                out[i] += memoryview(b) if isinstance(b, np.ndarray) else b
        return [bytes(b) for b in out]

    def _encode_stream(self, data: bytes, k: int, m: int,
                       algo: str | None = None):
        """Yield lists of n framed shard-chunks per batch of blocks
        from an in-memory object (small/compat path)."""
        chunks = streams.batched_chunks(data, None,
                                        BATCH_BLOCKS * BLOCK_SIZE)
        yield from self._encode_chunks(chunks, k, m, algo)

    def build_ladder(self, parity: int | None = None) -> None:
        """Ask for the shape ladder of the device programs this set's
        PUTs and GETs run at `parity` (None: the set's default), see
        `ShardMath.build_ladder`."""
        m = self.clamp_parity(parity)
        self.math.build_ladder(self.n - m, m)

    def _encode_chunks(self, chunks, k: int, m: int,
                       algo: str | None = None,
                       double_buffer: bool = False):
        """Encode an iterator of (chunk, is_last) pairs — every chunk a
        multiple of BLOCK_SIZE except the final one — yielding lists of
        n framed shard-chunks.  Memory is O(chunk), never O(object).

        Full 1 MiB blocks are encoded as one batch ((B, K, S) uint8) on
        the plane the set's shard math runs on, through a one-deep
        `pending` pipeline (`shardmath.Encoder`); the partial tail
        block goes through the CPU oracle codec (tiny, not worth a
        dispatch).

        ``double_buffer=True`` makes every yielded batch safe to consume
        asynchronously while the NEXT batch encodes (a pipelined caller
        that overlaps shard writes of batch *i* with the encode of batch
        *i+1*).
        """
        if algo is None:
            algo = bitrot_io.write_algo()
        shard_size = -(-BLOCK_SIZE // k)
        enc = self.math.encoder(k, m, algo, double_buffer)
        pending = None
        for chunk, is_last in chunks:
            buf = np.frombuffer(chunk, dtype=np.uint8)
            n_full = buf.size // BLOCK_SIZE
            for start in range(0, n_full, BATCH_BLOCKS):
                nb = min(BATCH_BLOCKS, n_full - start)
                batch = buf[start * BLOCK_SIZE:(start + nb) * BLOCK_SIZE]
                if BLOCK_SIZE % k == 0:
                    blocks = batch.reshape(nb, k, shard_size)
                else:
                    # Non-power-of-two K: each block zero-pads to
                    # K*shard_size (split padding rule,
                    # cf. erasure-coding.go:81).
                    with ospan.span("engine.stage"):
                        blocks = np.zeros((nb, k * shard_size),
                                          dtype=np.uint8)
                        blocks[:, :BLOCK_SIZE] = batch.reshape(
                            nb, BLOCK_SIZE)
                        blocks = blocks.reshape(nb, k, shard_size)
                    DATA_PATH.record_stage_pad(batch.size)
                nxt = enc.encode(blocks)
                if pending is not None:
                    yield enc.frames(pending)
                pending = nxt
                if not enc.overlaps:
                    yield enc.frames(pending)
                    pending = None

            tail = buf[n_full * BLOCK_SIZE:]
            if is_last:
                if pending is not None:
                    yield enc.frames(pending)
                    pending = None
                if tail.size:
                    cpu = self.math.cpu(k, m)
                    shards = cpu.encode_data(tail.tobytes())  # k+m arrays
                    tail_shard = shards[0].size
                    yield [bitrot_io.frame_shard(s, tail_shard, algo)
                           for s in shards]
            if not is_last and tail.size:
                raise ValueError("non-final chunk not BLOCK_SIZE aligned")

    # -- get -----------------------------------------------------------------

    def get_object(self, bucket: str, obj: str, offset: int = 0,
                   length: int = -1, version_id: str = "") -> tuple[FileInfo, bytes]:
        """Read [offset, offset+length) of an object, verifying bitrot and
        reconstructing up to `parity` missing/corrupt shards.

        With a hot tier attached, the read is served from the shared
        RAM cache when fresh, and cold reads of cacheable objects run
        single-flight: one leader does the engine read (and fills the
        cache if every segment passed the full-k fast-path verify),
        concurrent followers slice the leader's result.

        cf. GetObjectNInfo → getObjectWithFileInfo,
        /root/reference/cmd/erasure-object.go:221.
        """
        if self.hot_tier is not None and self.hot_tier.enabled:
            got = self._get_object_hot(bucket, obj, offset, length,
                                       version_id)
            if got is not None:
                return got
        return self._get_object_direct(bucket, obj, offset, length,
                                       version_id)

    def _get_object_direct(self, bucket: str, obj: str, offset: int = 0,
                           length: int = -1, version_id: str = "",
                           report: dict | None = None
                           ) -> tuple[FileInfo, bytes]:
        """The uncached engine read: segment reads assemble straight
        into ONE preallocated bytearray (each `_read_part` gathers into
        its slice of the final buffer), so the object is never joined
        through an extra full-size copy; the return is that
        memoryview-backed bytearray (bytes-compatible for
        hashing/slicing/IO).

        `report` (hot-tier fill eligibility) collects per-read
        evidence: segs = segment count, fast = segments served by the
        full-k verify-only fast path, taint = any decode/reconstruct
        involvement.  Fill requires fast == segs and no taint.
        """
        fi, metas, offset, length = self._plan_read(bucket, obj, offset,
                                                    length, version_id)
        if length == 0:
            return fi, b""
        data = self._read_whole_small(bucket, obj, fi, metas, version_id)
        if data is not None:
            if offset == 0 and length == len(data):
                return fi, data
            # Ranged inline/v1 reads serve a memoryview SLICE of the
            # already-materialized body: every consumer (socket writer,
            # hashing, bytes()) takes any buffer, so the per-request
            # copy was pure CPU tax.  MTPU_ZEROCOPY=0 keeps the copying
            # bytes slice as the byte-identical oracle.
            if zc.zerocopy_enabled():
                return fi, memoryview(data)[offset:offset + length]
            return fi, data[offset:offset + length]

        # The zeroed destination buffer is real time at 10s of MiB
        # (~0.3 ms/MiB of page faults) — price it as its own stage.
        with ospan.span("engine.alloc"):
            buf = bytearray(length)
        DATA_PATH.record_get_fresh_buffer("response", length)
        mv = memoryview(buf)
        segs = self._plan_segments(fi, offset, length)
        offs = []
        o = 0
        for _, _, ln in segs:
            offs.append(o)
            o += ln
        degraded = (any(d is None for d in self.drives)
                    or any(m is None for m in metas))
        if report is not None:
            report["segs"] = len(segs)
            if degraded:
                report["taint"] = True

        def read_seg(i):
            pn, off, ln = segs[i]
            with ospan.span("engine.read_part"):
                self._read_part(bucket, obj, fi, part_number=pn,
                                offset=off, length=ln,
                                dst=mv[offs[i]:offs[i] + ln],
                                healthy=not degraded, report=report)
        if self._serial_local() and not degraded:
            for i in range(len(segs)):
                read_seg(i)
        else:
            for _ in pl.prefetch_map(ospan.wrap_ctx(read_seg),
                                     range(len(segs)),
                                     self._iter_pool, depth=1):
                pass
        return fi, buf

    # -- hot tier ------------------------------------------------------------

    @staticmethod
    def _hot_range(fi, body, offset: int, length: int):
        """Slice a cached/leader whole-object body with _plan_read's
        exact range-validation semantics, so a cache hit raises the
        same errors a direct read would."""
        size = fi.size
        if offset < 0 or offset > size:
            raise StorageError(
                f"offset {offset} outside object of size {size}")
        if length < 0:
            length = size - offset
        if offset + length > size:
            raise StorageError(f"range [{offset}, {offset + length}) "
                               f"outside object of size {size}")
        if offset == 0 and length == len(body):
            return body
        return body[offset:offset + length]

    def _hot_cacheable(self, fi) -> bool:
        """Only healthy streaming-layout objects within the size gate
        enter the cache: inline/v1 small objects are already a single
        cheap read, and zero-byte bodies carry no payload to cache."""
        from ..storage import xlmeta_v1
        if fi.deleted or fi.size <= 0 \
                or fi.size > self.hot_tier.max_obj:
            return False
        if fi.inline_data is not None or (fi.parts and not fi.data_dir):
            return False
        return not xlmeta_v1.is_v1(fi)

    def _get_object_hot(self, bucket: str, obj: str, offset: int,
                        length: int, version_id: str,
                        skip_lookup: bool = False):
        """Hot-tier GET: cache hit, else single-flight engine read with
        a verified fill.  Returns (fi, body) or None — None means
        \"bypass: caller must do the direct read\"."""
        tier = self.hot_tier
        if not skip_lookup:
            got = tier.lookup(bucket, obj, version_id)
            if got is not None:
                fi, body = got
                return fi, self._hot_range(fi, body, offset, length)
        key = (id(self), bucket, obj, version_id)
        flight, leader = tier.flights.begin(key)
        if not leader:
            res = flight.wait()
            if res is None:
                return None         # leader failed/bypassed: go direct
            fi, body = res
            return fi, self._hot_range(fi, body, offset, length)
        ok = False
        try:
            # Capture the bucket generation BEFORE the read: a write
            # landing mid-read bumps it and the fill is discarded.
            gen0 = tier.generation(bucket)
            fi, metas, _, _ = self._plan_read(bucket, obj, 0, -1,
                                              version_id)
            if not self._hot_cacheable(fi):
                tier.note_bypass()
                return None
            report: dict = {}
            fi, data = self._get_object_direct(bucket, obj, 0, -1,
                                               version_id,
                                               report=report)
            body = bytes(data)
            if report.get("segs") and not report.get("taint") \
                    and report.get("fast", 0) == report["segs"]:
                tier.fill(bucket, obj, version_id, fi, body, gen0)
            else:
                tier.note_bypass()
            flight.resolve((fi, body))
            ok = True
            return fi, self._hot_range(fi, body, offset, length)
        finally:
            if not ok:
                flight.resolve(None)
            tier.flights.end(key)

    def _plan_read(self, bucket, obj, offset, length, version_id):
        """Shared GET front half: cached metadata election + range
        validation.  Returns (fi, metas, offset, resolved_length)."""
        fi, metas, errs = self._read_metadata_cached(bucket, obj,
                                                     version_id)
        if fi.deleted:
            raise ErrObjectNotFound(f"{bucket}/{obj} (delete marker)")
        size = fi.size
        if offset < 0 or offset > size:
            raise StorageError(f"offset {offset} outside object of size {size}")
        if length < 0:
            length = size - offset
        if offset + length > size:
            raise StorageError(f"range [{offset}, {offset + length}) "
                               f"outside object of size {size}")
        if size == 0:
            length = 0
        return fi, metas, offset, length

    def _read_whole_small(self, bucket, obj, fi, metas, version_id):
        """Inline / legacy-v1 whole-object read, or None for the
        streaming erasure layout."""
        if fi.inline_data is not None or (fi.parts and not fi.data_dir):
            return self._read_inline(bucket, obj, fi, metas, version_id)
        from ..storage import xlmeta_v1
        if xlmeta_v1.is_v1(fi):
            # Legacy format-v1 object: unframed shard files with
            # whole-file bitrot, 10 MiB blocks (migration read path,
            # cmd/xl-storage-format-v1.go + cmd/bitrot-whole.go).
            return self._read_v1_object(bucket, obj, fi)
        return None

    def _plan_segments(self, fi, offset: int,
                       length: int) -> list[tuple[int, int, int]]:
        """Map an object byte range onto batch-aligned per-part segments.

        Segment size: `ShardMath.segment_blocks`.  Each part is an
        independent EC stream (cf. ObjectToPartOffset,
        cmd/erasure-metadata.go)."""
        batch_bytes = self.math.segment_blocks() * BLOCK_SIZE
        segs: list[tuple[int, int, int]] = []   # (part_number, off, len)
        part_start = 0
        remaining = length
        pos = offset
        for part in fi.parts:
            part_end = part_start + part.size
            if remaining <= 0:
                break
            if pos < part_end:
                in_off = pos - part_start
                in_len = min(remaining, part.size - in_off)
                seg = in_off
                stop = in_off + in_len
                while seg < stop:
                    # segment ends at the next batch boundary so each
                    # yield is one bounded device dispatch
                    boundary = (seg // batch_bytes + 1) * batch_bytes
                    seg_end = min(stop, boundary)
                    segs.append((part.number, seg, seg_end - seg))
                    seg = seg_end
                pos += in_len
                remaining -= in_len
            part_start = part_end
        return segs

    def get_object_iter(self, bucket: str, obj: str, offset: int = 0,
                        length: int = -1, version_id: str = ""):
        """Streaming read: returns (fi, iterator of assembled byte
        chunks), each chunk one device batch (<= BATCH_BLOCKS blocks) of
        verified+decoded data — memory is O(batch), never O(object)
        (the GetObjectReader role, cmd/object-api-utils.go:392-528)."""
        if self.hot_tier is not None and self.hot_tier.enabled:
            tier = self.hot_tier
            if zc.zerocopy_enabled() \
                    and hasattr(tier, "lookup_view"):
                # Zero-copy hit: the chunk is an ndarray view pinned
                # over the shared arena (release rides the view's GC;
                # eviction under the pin only defers slot reuse).  The
                # socket writer sends it via sendmsg without any
                # bytes() materialization — ranged GETs slice the view,
                # not copy it.
                got = tier.lookup_view(bucket, obj, version_id)
                if got is not None:
                    hfi, body = got
                    chunk = self._hot_range(hfi, body, offset, length)
                    DATA_PATH.record_zerocopy_hot_view(len(chunk))
                    return hfi, (iter(()) if len(chunk) == 0
                                 else iter((chunk,)))
            else:
                got = tier.lookup(bucket, obj, version_id)
                if got is not None:
                    hfi, body = got
                    chunk = self._hot_range(hfi, memoryview(body),
                                            offset, length)
                    return hfi, (iter(()) if len(chunk) == 0
                                 else iter((chunk,)))
            got = None
            # Cold cacheable object: delegate to the single-flight
            # whole-read (fills the cache; O(max_obj) memory is the
            # admission bound, so streaming degrades to nothing).
            # skip_lookup — the miss was already counted above.
            try:
                peek, _, _, _ = self._plan_read(bucket, obj, 0, -1,
                                                version_id)
            except StorageError:
                peek = None
            if peek is not None and self._hot_cacheable(peek):
                got = self._get_object_hot(bucket, obj, offset, length,
                                           version_id, skip_lookup=True)
                if got is not None:
                    hfi, body = got
                    return hfi, (iter(()) if len(body) == 0
                                 else iter((body,)))
            elif peek is not None:
                tier.note_bypass()
        fi, metas, offset, length = self._plan_read(bucket, obj, offset,
                                                    length, version_id)
        if length == 0:
            return fi, iter(())

        data = self._read_whole_small(bucket, obj, fi, metas, version_id)
        if data is not None:
            if offset == 0 and length == len(data):
                return fi, iter((data,))
            # Zero-copy range: the consumer (socket writer) takes any
            # buffer, so slice through a memoryview instead of copying.
            return fi, iter((memoryview(data)[offset:offset + length],))

        segs = self._plan_segments(fi, offset, length)

        # One-segment prefetch: segment i+1's drive reads + fused
        # verify/decode dispatch run while segment i drains to the
        # caller — hides device round-trips (the host↔device boundary,
        # not yet measured on this machine) behind socket writes.  On a
        # 1-core host with local drives a HEALTHY read has nothing to
        # overlap — prefetch is
        # pure executor overhead, so segments run inline.  A DEGRADED
        # read is different even there: reconstruction is native
        # GIL-releasing kernel work, so segment i+1's shard reads run
        # under segment i's decode (the reconstruct-pipeline shape
        # heal uses, parallel/pipeline.py).
        degraded = (any(d is None for d in self.drives)
                    or any(m is None for m in metas))
        pool = (None if self._serial_local() and not degraded
                else self._iter_pool)

        def read_seg(seg):
            pn, off, ln = seg
            with ospan.span("engine.read_part"):
                return self._read_part(bucket, obj, fi, part_number=pn,
                                       offset=off, length=ln,
                                       healthy=not degraded)
        return fi, pl.prefetch_map(ospan.wrap_ctx(read_seg), segs, pool,
                                   depth=1)

    def sendfile_plan(self, bucket: str, obj: str, offset: int = 0,
                      length: int = -1, version_id: str = ""):
        """Kernel-send plan for a whole healthy GET, or None.

        When the object's framing allows it — k=1 layout, so each
        part's single data shard IS the plaintext interleaved with
        bitrot frames — the response body can leave via os.sendfile of
        the data runs: the bytes go page cache -> socket without ever
        entering the process.  Returns (fi, [FilePlan, ...]) with the
        shard files ALREADY digest-verified through an mmap over the
        same fds the sends will use (a racing delete only unlinks the
        name), or None when any gate fails — the caller then takes the
        normal engine read, so this is a pure opportunistic overlay.

        Gates: MTPU_ZEROCOPY on; whole object (offset 0, full length);
        k=1 streaming layout (not inline, not legacy v1); nothing
        degraded; the shard drive is a healthy LocalDrive; and the
        object is NOT hot-cacheable when the RAM tier is on (the tier
        owns the small hot set — sendfile serves what the cache
        can't)."""
        if not zc.zerocopy_enabled():
            return None
        try:
            fi, metas, offset, length = self._plan_read(
                bucket, obj, offset, length, version_id)
        except StorageError:
            return None          # normal path surfaces the real error
        if offset != 0 or length != fi.size or fi.size <= 0:
            return None
        if fi.erasure.data_blocks != 1:
            return None
        if fi.inline_data is not None or not fi.parts \
                or not fi.data_dir:
            return None
        from ..storage import xlmeta_v1
        if xlmeta_v1.is_v1(fi):
            return None
        if self.hot_tier is not None and self.hot_tier.enabled \
                and self._hot_cacheable(fi):
            return None
        if any(m is None for m in metas) \
                or any(d is None for d in self.drives):
            return None
        order = Q.shuffle_by_distribution(list(range(self.n)),
                                          fi.erasure.distribution)
        d = self.drives[order[0]]
        if not isinstance(d, LocalDrive) or not drive_available(d):
            return None
        import mmap as _mmap
        shard_size = fi.erasure.shard_size
        plans: list[zc.FilePlan] = []
        try:
            for part in fi.parts:
                algo = fi.erasure.bitrot_algo(part.number)
                hs = bitrot_io.digest_size(algo)
                frame = hs + shard_size
                fd = d.open_read_fd(
                    bucket, f"{obj}/{fi.data_dir}/part.{part.number}")
                full = part.size // shard_size
                tail = part.size - full * shard_size
                runs = [(b * frame + hs, shard_size)
                        for b in range(full)]
                if tail:
                    runs.append((full * frame + hs, tail))
                # FilePlan owns the fd from here (closes on any bail).
                plan = zc.FilePlan(fd, runs, part.size)
                plans.append(plan)
                want = bitrot_io.bitrot_shard_file_size(
                    part.size, shard_size, algo)
                if os.fstat(fd).st_size != want:
                    raise ErrFileCorrupt("sendfile plan size mismatch")
                # Verify the framed shard through the SAME fd the sends
                # will use.  The mmap is dropped, not closed: numpy may
                # still export its buffer and GC unmaps it safely.
                mm = _mmap.mmap(fd, want, prot=_mmap.PROT_READ)
                bitrot_io.unframe_shard(memoryview(mm), shard_size,
                                        verify=True,
                                        logical_size=part.size,
                                        algo=algo)
        except (StorageError, OSError, ValueError):
            for p in plans:
                p.close()
            return None
        return fi, plans

    def _read_v1_object(self, bucket, obj, fi) -> bytes:
        """Whole-object read of a legacy (xl.json) object: per-drive
        UNFRAMED part files verified by whole-file digest, per-block
        reconstruction via the CPU oracle (v1 is a migration path, not
        a hot path)."""
        k, m = fi.erasure.data_blocks, fi.erasure.parity_blocks
        bs = fi.erasure.block_size
        dist = fi.erasure.distribution
        out = bytearray()
        from ..storage import xlmeta_v1
        # v1 checksums are per-drive: each drive's xl.json carries the
        # whole-file hash of ITS shard — parse once per drive, not once
        # per (drive, part).
        own_sums: list[list[dict] | None] = []
        for d in self.drives:
            if d is None:
                own_sums.append(None)
                continue
            try:
                own = xlmeta_v1.parse_xl_json(
                    d.read_all(bucket, f"{obj}/{xlmeta_v1.XL_JSON}"),
                    bucket, obj)
                own_sums.append(own.erasure.checksums)
            except StorageError:
                # No readable xl.json = no digest to verify against:
                # treat the drive's shards as MISSING and reconstruct
                # around them — serving unverifiable bytes risks silent
                # corruption (the drive most likely to have lost its
                # metadata is the damaged one).
                own_sums.append(None)

        for part in fi.parts:

            def read_row(pos: int):
                d = self.drives[pos]
                if d is None or own_sums[pos] is None:
                    return None                   # offline/unverifiable
                try:
                    raw = d.read_file(bucket,
                                      f"{obj}/part.{part.number}")
                except StorageError:
                    return None
                # A shard we cannot verify is a shard we must not
                # trust: a part with no (or an empty) recorded digest
                # is treated like a missing xl.json above — return
                # None and reconstruct around it.
                for c in own_sums[pos]:
                    if c.get("name") == f"part.{part.number}" \
                            and c.get("hash"):
                        algo = c.get("algo", "highwayhash256")
                        if bitrot_io.whole_file_digest(
                                raw, algo) != c["hash"]:
                            return None           # corrupt shard
                        return raw
                return None                       # unverifiable shard

            rows: list[bytes | None] = [None] * (k + m)
            for pos in range(self.n):
                if pos < len(dist):
                    raw = read_row(pos)
                    if raw is not None:
                        rows[dist[pos] - 1] = raw
            if sum(1 for r in rows if r is not None) < k:
                raise ErrErasureReadQuorum(
                    f"{bucket}/{obj} part {part.number} (v1)")
            # Per-block chunks: v1 sizes each block's shard as
            # ceil(cur_block/k) with the final block shorter.
            remaining = part.size
            offs = [0] * (k + m)
            while remaining > 0:
                cur = min(bs, remaining)
                chunk = -(-cur // k)
                block_rows: list[np.ndarray | None] = []
                for s, r in enumerate(rows):
                    if r is None:
                        block_rows.append(None)
                        continue
                    block_rows.append(np.frombuffer(
                        r[offs[s]:offs[s] + chunk], dtype=np.uint8))
                    offs[s] += chunk
                missing = [s for s in range(k) if block_rows[s] is None]
                if missing:
                    rec = self.math.cpu(k, m).reconstruct(block_rows,
                                                      data_only=True)
                    for s in missing:
                        block_rows[s] = rec[s]
                blk = np.concatenate(block_rows[:k])[:cur]
                out += blk.tobytes()
                remaining -= cur
        return bytes(out)

    def _read_metadata(self, bucket, obj, version_id=""):
        """Read xl.meta from all N drives, once each, and elect.

        How the N reads run follows from what the drives are: drives
        of this process are read on the calling thread, one after
        another; remote drives on the pool, where network round trips
        overlap.  A page-cache read is ~0.05 ms of work; what it costs
        under concurrent clients is every point where its thread gives
        the GIL away (~0.7 ms each with 8 clients on the chip host,
        PERF.md §6, PR 30), and a hand-off to a lane or pool thread
        only adds such points.  A local drive that turns slow is the
        breaker's business (health_wrap times every call), as at every
        other serial-local site."""
        version_id = normalize_version_id(version_id)
        inline = self._in_process()
        path = "inline" if inline else "pool"
        DATA_PATH.record_meta_read_request(path)
        with ospan.span("engine.quorum") as sp:
            sp.tag(path=path)
            res = self._map_drives(
                lambda d: d.read_version(bucket, obj, version_id),
                inline=inline)
        metas = [fi for fi, _ in res]
        errs = [e for _, e in res]
        n_found = sum(1 for f in metas if f is not None)
        if n_found == 0:
            err, count = Q.reduce_errs(errs, ignored=(ErrDiskNotFound,))
            if isinstance(err, (ErrFileNotFound, ErrVolumeNotFound)):
                if not self.bucket_exists(bucket):
                    raise ErrBucketNotFound(bucket)
                raise ErrObjectNotFound(f"{bucket}/{obj}")
            if isinstance(err, ErrFileVersionNotFound):
                raise ErrVersionNotFound(f"{bucket}/{obj}@{version_id}")
            raise ErrErasureReadQuorum(f"{bucket}/{obj}: {err}")
        read_quorum, _ = Q.object_quorum_from_meta(
            metas, self.n, self.default_parity)
        fi = Q.find_file_info_in_quorum(metas, read_quorum)
        return fi, metas, errs

    def _fi_cache_store(self, bucket, obj, version_id, entry) -> None:
        # Bounded LRU: evict oldest-touched entries one at a time
        # (dict preserves insertion order; _read_metadata_cached
        # reinserts on hit, so iteration order IS recency order).  The
        # previous clear()-at-capacity wiped every hot entry whenever
        # a key scan overflowed the cache, zeroing the hit ratio.
        cache = self._fi_cache
        key = (bucket, obj, normalize_version_id(version_id))
        # Pop first: overwriting an existing dict key keeps its OLD
        # insertion slot, which would pin a re-stored hot entry at the
        # LRU end forever.
        cache.pop(key, None)
        while len(cache) >= self._FI_CACHE_MAX:
            try:
                cache.pop(next(iter(cache)))
            except (StopIteration, KeyError, RuntimeError):
                break  # racing eviction/clear — capacity is advisory
        cache[key] = (self._fi_gen.get(bucket, 0),
                      time.monotonic(), *entry)

    def _read_metadata_cached(self, bucket, obj, version_id=""):
        """GET-path metadata election with the parsed-quorum cache: a
        ranged GET fanned out as N segment requests (or HEAD followed by
        GET in the same request) elects xl.meta once, not N times.  Any
        write through this set bumps the bucket generation (_mark_dirty)
        and invalidates immediately; a short TTL bounds what another
        process's write can leave stale, same policy as bucket_exists."""
        key = (bucket, obj, normalize_version_id(version_id))
        hit = self._fi_cache.pop(key, None)
        if hit is not None:
            gen, stamp, fi, metas, errs = hit
            if (gen == self._fi_gen.get(bucket, 0)
                    and time.monotonic() - stamp < self._FI_CACHE_TTL):
                # Reinsert: a hit moves the entry to the MRU end so
                # LRU eviction tracks touch order, not insert order.
                self._fi_cache[key] = hit
                return fi, metas, errs
        entry = self._read_metadata(bucket, obj, version_id)
        self._fi_cache_store(bucket, obj, version_id, entry)
        return entry

    def _read_inline(self, bucket, obj, fi, metas, version_id) -> bytes:
        k, m = fi.erasure.data_blocks, fi.erasure.parity_blocks
        dist = fi.erasure.distribution
        # Gather each drive's inline shard (already framed).
        shard_bytes: list[bytes | None] = [None] * (k + m)
        want_key = Q._fi_key(fi)
        for pos, meta in enumerate(metas):
            # Only trust shards from drives whose metadata matches the
            # elected version — a stale drive's inline shard is internally
            # consistent and would silently corrupt the read.
            if (meta is not None and meta.inline_data is not None
                    and Q._fi_key(meta) == want_key):
                shard_bytes[dist[pos] - 1] = meta.inline_data
        return self._decode_shard_files(shard_bytes, fi, fi.size)

    def _read_part(self, bucket, obj, fi, part_number, offset, length,
                   dst=None, healthy=None, report=None):
        """Ranged read of one part: fetch only the frames covering the
        block range, then run bitrot verify + reconstruction of missing
        rows as ONE fused device dispatch (north-star config #5; the
        parallelReader analogue of cmd/erasure-decode.go:101 with the
        verifying ReadAt of cmd/bitrot-streaming.go:142 moved on-device).

        HEALTHY reads (all k data shards present, metas agreed) take the
        verify-only fast path instead: batched bitrot VERDICTS (fused
        host kernel / device digests / pooled HighwayHash) plus a
        systematic gather — zero GF(2^8) work, since the data shards of
        a systematic code already are the plaintext.  Any verify or read
        failure falls back to the decode path below, which is also the
        byte-exactness oracle (MTPU_GET_FASTPATH=0).

        `dst`: optional writable memoryview of exactly `length` bytes;
        when given, the result is assembled straight into it (the
        get_object zero-copy assembly) and None is returned.  `healthy`:
        tri-state hint from the caller's metadata election (False =
        metas disagreed somewhere, skip the fast path).

        A digest mismatch is handled exactly like an I/O failure: the
        corrupt row is dropped and a spare shard is fetched.
        """
        k, m = fi.erasure.data_blocks, fi.erasure.parity_blocks
        dist = fi.erasure.distribution
        part_size = fi.parts[part_number - 1].size
        shard_size = fi.erasure.shard_size
        algo = fi.erasure.bitrot_algo(part_number)
        hs = bitrot_io.digest_size(algo)
        b0 = offset // BLOCK_SIZE
        b1 = -(-(offset + length) // BLOCK_SIZE)
        frame = hs + shard_size
        path = f"{obj}/{fi.data_dir}/part.{part_number}"
        geo = self._range_geometry(fi, part_size, b0, b1)
        nb = geo["nb_full"]
        has_tail, tail_shard = geo["has_tail"], geo["tail_shard"]
        # Host fast path: shard files mmap'd straight into the fused
        # native verify+gather+reconstruct kernel — object bytes are
        # never copied by Python and never cross read().
        fused_host = self.math.host_fused(k, m, algo)
        # Device-resident shard cache (ops/devcache.py): generation is
        # captured BEFORE any shard read so a racing write invalidates
        # the fill rather than the fill masking the write.  Only fully
        # verified fast-path reads fill; hits serve the verified host
        # copy with zero disk reads, zero uploads, zero dispatches.
        dcache = devcache_mod.get() if devcache_mod.enabled() else None
        dc_gen0 = (dcache.current_gen(self._devcache_owner, bucket)
                   if dcache is not None else 0)

        # A shard's segment: nb full frames, then the tail's frame, which
        # ends the shard file.
        expect = nb * frame + ((hs + tail_shard) if has_tail else 0)

        def parse_row(buf):
            """(hashes (nb, 32), blocks (nb, S), tail or None, raw): views
            of one shard's segment `buf` (`expect` bytes of uint8).  Full
            blocks are NOT hash-verified here — that happens batched on
            device (or in the fused native pass, which consumes `raw`).
            The (tiny) tail fragment verifies on host immediately.
            Views, no copy: the selected rows are gathered into one
            contiguous (nb, K, S) buffer in a single strided pass below
            — copying here would double the memory traffic."""
            frames = buf[:nb * frame].reshape(nb, frame)
            tail = None
            if has_tail:
                tail = bitrot_io.unframe_shard(
                    buf[nb * frame:].tobytes(), tail_shard, verify=True,
                    algo=algo)
            return frames[:, :hs], frames[:, hs:], tail, buf[:nb * frame]

        def read_shard(pos: int):
            """Fetch + structurally parse one shard's frame range: a
            drive call (`parse_row`'s views of what it returned).
            Successful reads feed the per-position EWMA that drives
            hedge ignition on serial hosts (failures don't: a fast
            error must not make a drive look fast).
            """
            t_rs = time.monotonic()
            d = self.drives[pos]
            if d is None:
                raise ErrDiskNotFound("offline")
            if fused_host is not None and isinstance(d, LocalDrive):
                raw = d.read_file_view(bucket, path, b0 * frame,
                                       (b1 - b0) * frame)
            else:
                raw = d.read_file(bucket, path, b0 * frame,
                                  (b1 - b0) * frame)
            buf = np.frombuffer(raw, dtype=np.uint8)
            if buf.size != expect:
                raise ErrFileCorrupt(
                    f"shard segment {buf.size} != expected {expect}")
            row = parse_row(buf)
            self._note_read_ms(pos, (time.monotonic() - t_rs) * 1e3)
            DATA_PATH.record_shard_rows("pool", 1)
            return row

        def read_batched(first, then):
            """Shards `first`, and for each that fails the next of `then`,
            in ONE native call (drive.read_rows): the K rows a round
            needs, read into one leased buffer with the GIL released
            once.  Fills `rows` and `tried` as a round of read_shard
            calls does; a failed read or tail lands in `tried` alone."""
            if not first:
                return
            shards = [*first, *then]
            out = segarena.lease(len(first) * expect, "read")
            got = read_rows(
                [self.drives[order[s]] for s in shards], bucket, path,
                b0 * frame, expect, len(first), out, exact_end=has_tail)
            fetched = 0
            for s, (j, err, secs) in zip(shards, got):
                if j is None and err is None:
                    continue                    # not tried: K were in
                tried.add(s)
                if err is not None:
                    continue
                try:
                    rows[s] = parse_row(out[j * expect:(j + 1) * expect])
                except StorageError:            # the tail's digest
                    continue
                fetched += 1
                self._note_read_ms(order[s], secs * 1e3)
            DATA_PATH.record_shard_rows("batched", fetched)

        order = Q.shuffle_by_distribution(list(range(self.n)), dist)
        # order[s] = drive position holding shard s. Data shards first,
        # parity as spares (cf. preferReaders, cmd/erasure-decode.go:101).
        rows: dict[int, tuple] = {}
        tried: set[int] = set()
        # Offline drives — physical holes AND breaker-open circuits —
        # can never yield a shard: skipping them up front means a
        # degraded read goes straight to the parity spares instead of
        # burning a retry round per dead position.
        candidates = [s for s in range(k + m)
                      if drive_available(self.drives[order[s]])]
        degraded = any(s < k for s in range(k + m) if s not in candidates)
        t_deg = time.monotonic() if degraded else 0.0
        # Each round's rows come from one native call where every
        # candidate is a drive of this process and no fused host pass
        # reads mmap views of them (a slow local drive is its breaker's
        # business, as at every serial-local site).  Remote drives, the
        # host-fused plane, O_DIRECT and a host with no toolchain read a
        # row a drive call, through the pool and the hedge.
        batched = fused_host is None and rows_readable(
            [self.drives[order[s]] for s in candidates])
        lo = offset - b0 * BLOCK_SIZE
        full_bytes = nb * k * shard_size   # nb * BLOCK_SIZE where K divides it
        aligned = dst is not None and lo == 0 and length >= full_bytes

        def deliver(y, tail_np, placed=False):
            """The read's range of the verified rows `y` and the tail
            fragment: into `dst` where given (`placed`: `y` lies there
            already), else returned."""
            if aligned:
                if nb and not placed:
                    dst[:full_bytes] = memoryview(y.reshape(-1))
                if tail_np is not None and length > full_bytes:
                    dst[full_bytes:length] = memoryview(
                        np.ascontiguousarray(
                            tail_np[:length - full_bytes]))
                return None
            # K divides the block on this read: a row of `y` is a block.
            return _join_range(y.reshape(nb, BLOCK_SIZE) if nb else None,
                               tail_np, lo, length, dst)[0]

        def fast_path():
            """Verify-only healthy read.  Returns (res,) on success or
            None to fall back (bad rows already dropped from `rows` so
            the decode loop goes straight to the parity spares)."""
            t0 = time.monotonic()
            want = [s for s in range(k) if s not in rows]
            # Hedge gate: pool fan-out hosts hedge by default; the
            # 1-core serial host ignites only when the EWMAs show a
            # straggler (otherwise serial page-cache reads win).
            use_hedge = (
                _hedge_enabled() and want and not self._on_drive_pool()
                and (not self._serial_local()
                     or self._hedge_worthwhile([order[s] for s in want])))
            spares = [s for s in candidates if s >= k and s not in rows]
            if batched or use_hedge:
                if batched:
                    read_batched(want, spares)
                else:
                    abandoned = self._hedged_fetch(
                        read_shard, order, rows, tried, want, spares, k)
                    for s in abandoned:
                        tried.discard(s)
                if any(s not in rows for s in range(k)):
                    # A data read failed and a spare took its slot (or a
                    # parity spare won the race): the row set isn't
                    # purely systematic, so the decode loop below
                    # reconstructs from these rows — no re-read, just GF
                    # work for the holes.
                    return None
            elif self._serial_local() or self._on_drive_pool():
                tried.update(want)
                for s in want:
                    rows[s] = read_shard(order[s])
            else:
                tried.update(want)
                rs = ospan.wrap_ctx(read_shard)
                futs = {s: self.pool.submit(rs, order[s])
                        for s in want}
                first_err = None
                for s, fut in futs.items():
                    try:
                        rows[s] = fut.result()
                    except Exception as e:  # noqa: BLE001
                        first_err = first_err or e
                if first_err is not None:
                    raise first_err
            body = dst[:full_bytes] if aligned else None
            t_read = time.monotonic()
            asm_s = 0.0
            y = None
            use_co = self.math.digest_rides(nb, algo)
            DATA_PATH.record_verify_blocks(nb)
            if nb and fused_host is not None:
                # mxh256 host: ONE C pass verifies every frame AND
                # gathers the systematic rows straight into the final
                # object buffer — targets=[] means the GF unit is never
                # entered (verify time below includes that gather).
                y, okf, nbad = fused_host.get_verify(
                    [rows[s][3] for s in range(k)], list(range(k)),
                    nb, shard_size, k, m, [], out=body)
                DATA_PATH.record_host_hash("get", nb * k * shard_size)
                if body is None:
                    DATA_PATH.record_get_fresh_buffer("assemble", y.nbytes)
                if nbad:
                    for j in range(k):
                        if not okf[j]:
                            del rows[j]
                    return None
            elif nb:
                # Gather first (it IS the assembly either way), then
                # hash-verify: the device kernel returns verdict
                # digests only — no decoded blocks cross back — and
                # HighwayHash/host algos digest the mmap'd frames in
                # place via the strided kernel on the worker pool.
                tg = time.monotonic()
                if body is not None:
                    y = np.frombuffer(body, dtype=np.uint8).reshape(
                        nb, k, shard_size)
                else:
                    y = segarena.lease(full_bytes, "assemble").reshape(
                        nb, k, shard_size)
                for s in range(k):
                    y[:, s, :] = rows[s][1]
                asm_s += time.monotonic() - tg
                ospan.record("engine.assemble", asm_s)
                digests = self.math.digest(y, k, m, algo, use_co)
                if digests is not None:
                    got = [digests[:, s] for s in range(k)]
                else:
                    with ospan.span("engine.hash") as sp:
                        sp.tag(bytes=nb * k * shard_size)
                        got = self._hash_shard_frames(
                            [rows[s][3] for s in range(k)], nb,
                            shard_size, hs, algo)
                    DATA_PATH.record_host_hash("get", nb * k * shard_size)
                bad = [s for s in range(k)
                       if not np.array_equal(got[s], rows[s][0])]
                if bad:
                    for s in bad:
                        del rows[s]
                    return None
            t_verify = time.monotonic()
            ta = t_verify
            tail_np = None
            if has_tail:
                tail_np = np.concatenate([rows[s][2] for s in range(k)])
                DATA_PATH.record_get_fresh_buffer("assemble",
                                                  tail_np.nbytes)
                tail_np = tail_np[:geo["tail_len"]]
            res = deliver(y, tail_np, placed=True)
            done = time.monotonic()
            DATA_PATH.record_healthy_read(
                length, read_s=t_read - t0, verify_s=t_verify - t_read,
                assemble_s=asm_s + (done - ta))
            # The same clock reads place the stages on the request's
            # timeline; the gather recorded above nests under the
            # verify it is part of, as do the drive reads and the
            # coalescer wait under theirs.
            ospan.bracket("engine.read", t0, t_read)
            ospan.bracket("engine.verify", t_read, t_verify)
            ospan.bracket("engine.assemble", ta, done)
            if report is not None:
                # Hot-tier evidence: this segment was served purely by
                # the full-k verify (dict ops are GIL-atomic enough for
                # the prefetch pool's one-writer-per-segment pattern).
                report["fast"] = report.get("fast", 0) + 1
            if dcache is not None and nb and y is not None:
                # Fill with private copies: `y` may view the caller's
                # dst buffer or a fused-host arena, and `tail_np` the
                # mmap'd frames — the cache must own its bytes.
                dcache.fill(
                    (self._devcache_owner, bucket, obj, part_number,
                     fi.data_dir, b0, b1, algo),
                    dc_gen0, np.array(y, copy=True),
                    tail=(np.array(tail_np, copy=True)
                          if tail_np is not None else None),
                    device=self.device_idx)
            return (res,)

        def devcache_hit(e, boff):
            """Assemble the read from a resident verified entry — the
            exact fast_path assembly over cached rows, no disk, no
            device, no dispatch.  Returns (res,) or None (entry lacks
            the tail fragment this range needs)."""
            t0 = time.monotonic()
            if has_tail and e.tail is None:
                return None
            y = e.host[boff:boff + nb] if nb else None
            tail_np = e.tail[:geo["tail_len"]] if has_tail else None
            res = deliver(y, tail_np)
            done = time.monotonic()
            DATA_PATH.record_healthy_read(
                length, read_s=0.0, verify_s=0.0, assemble_s=done - t0)
            ospan.record("engine.assemble", done - t0)
            if report is not None:
                report["fast"] = report.get("fast", 0) + 1
            return (res,)

        # BLOCK_SIZE % k gate: the padded (non-dividing k) layout needs
        # per-block trimming, which the generic assembly already does.
        if (_get_fastpath() and healthy is not False and not degraded
                and BLOCK_SIZE % k == 0
                and all(s in candidates for s in range(k))):
            if dcache is not None:
                found = dcache.lookup_range(
                    self._devcache_owner, bucket, obj, part_number,
                    fi.data_dir, algo, b0, b1)
                if found is not None:
                    got = devcache_hit(*found)
                    if got is not None:
                        return got[0]
            try:
                got = fast_path()
            except (StorageError, OSError):
                got = None
            if got is not None:
                return got[0]
            DATA_PATH.record_fastpath_fallback()

        if report is not None:
            # Decode/reconstruct involvement (fallback, degraded, or
            # fast path disabled): correct bytes, but not the full-k
            # verify-only read the hot tier requires for a fill.
            report["taint"] = True
        sel: list[int] = []
        missing: list[int] = []
        out = None
        y_fused = None
        while True:
            active = [s for s in candidates
                      if s not in tried and s not in rows][:max(k - len(rows), 0)]
            if len(rows) < k and not active:
                raise ErrErasureReadQuorum(
                    f"{bucket}/{obj}: only {len(rows)}/{k} shards readable")
            # A degraded read always fans out: the surviving-shard
            # fetches are mmap/pread + native digest work that release
            # the GIL, so overlapping them pays even on the 1-core host
            # (unlike the healthy path, where the K reads are page-cache
            # hits and pool hops only add latency).
            remaining = [s for s in candidates
                         if s not in tried and s not in rows
                         and s not in active]
            with ospan.span("engine.read"):
                if batched:
                    read_batched(active, remaining)
                elif (self._serial_local() and not degraded) \
                        or self._on_drive_pool():
                    for s in active:
                        tried.add(s)
                        try:
                            rows[s] = read_shard(order[s])
                        except Exception:  # noqa: BLE001 — spare read
                            pass
                elif _hedge_enabled():
                    # Hedged degraded fan-out: instead of a barrier on
                    # ALL active futures (one tail-slow survivor stalls
                    # the stripe), take the first k arrivals and cover
                    # stragglers/failures from the remaining spares.
                    abandoned = self._hedged_fetch(
                        read_shard, order, rows, tried, active,
                        remaining, k)
                    for s in abandoned:
                        tried.discard(s)
                else:
                    rs = ospan.wrap_ctx(read_shard)
                    futs = {}
                    for s in active:
                        tried.add(s)
                        futs[s] = self.pool.submit(rs, order[s])
                    for s, fut in futs.items():
                        try:
                            rows[s] = fut.result()
                        except Exception:  # noqa: BLE001 — spare read
                            pass
            if len(rows) < k:
                continue
            sel = sorted(rows)[:k]
            missing = [s for s in range(k) if s not in sel]
            if not nb:
                break
            if fused_host is not None:
                # ONE native pass over the mmap'd segments: digest every
                # chosen row, gather data rows, reconstruct the missing
                # ones. A digest mismatch surfaces exactly like an I/O
                # failure: drop the row, fetch a spare, run again.
                with ospan.span("engine.verify_decode"):
                    y_fused, okf, nbad = fused_host.get_verify(
                        [rows[s][3] for s in sel], sel, nb, shard_size,
                        k, m, missing)
                DATA_PATH.record_host_hash("get", nb * k * shard_size)
                DATA_PATH.record_verify_blocks(
                    nb, (k, m, tuple(sel), tuple(missing))
                    if missing else None)
                if nbad:
                    for j, s in enumerate(sel):
                        if not okf[j]:
                            del rows[s]
                    y_fused = None
                    continue
                break
            # ONE dispatch: digests of the K chosen rows + reconstruction
            # of the missing data rows from those same HBM-resident bytes.
            with ospan.span("engine.gather") as sp:
                x = segarena.lease(full_bytes, "gather").reshape(
                    nb, k, shard_size)
                for i, s in enumerate(sel):
                    x[:, i, :] = rows[s][1]                  # (nb, K, S)
                sp.tag(bytes=x.nbytes)
            with ospan.span("engine.verify_decode"):
                digests, out = self.math.verify_transform(
                    x, k, m, tuple(sel), tuple(missing), algo)
            bad = [sel[i] for i in range(k)
                   if not np.array_equal(digests[:, i], rows[sel[i]][0])]
            if not bad:
                break
            for s in bad:
                del rows[s]

        # Gather the K data rows in shard order. When nothing is
        # missing, sel IS [0..k), so x already holds them — the full
        # blocks then flow to the caller with no further copy (when
        # BLOCK_SIZE divides evenly, x's natural layout IS the data).
        with ospan.span("engine.assemble") as sp:
            copied = 0
            y = None
            if nb:
                if y_fused is not None:
                    y = y_fused
                    copied = y.nbytes
                    DATA_PATH.record_get_fresh_buffer("assemble", copied)
                elif not missing:
                    y = x
                else:
                    y = segarena.lease(full_bytes, "assemble").reshape(
                        nb, k, shard_size)
                    copied = y.nbytes
                    for s in range(k):
                        if s in sel:
                            y[:, s] = x[:, sel.index(s)]
                        else:
                            y[:, s] = out[missing.index(s)]
                # `x` goes back to the segment pool here unless `y` is
                # it, while the chunk is still on its way to the socket.
                x = None

            # Tail fragment: reconstruct missing rows via the CPU oracle
            # codec (a partial block is tiny — not worth a device
            # dispatch).
            tail_block = None
            if has_tail:
                tails = {s: rows[s][2] for s in sel}
                t_missing = [s for s in range(k) if s not in tails]
                if t_missing:
                    shards_in = [tails.get(s) for s in range(k + m)]
                    rec = self.math.cpu(k, m).reconstruct(shards_in,
                                                          data_only=True)
                    for s in t_missing:
                        tails[s] = rec[s]
                tail_block = np.concatenate([tails[s] for s in range(k)])
                copied += tail_block.nbytes
                DATA_PATH.record_get_fresh_buffer("assemble",
                                                  tail_block.nbytes)
                tail_block = tail_block[:geo["tail_len"]]
            sp.tag(bytes=copied)

        # The read's range of the assembled blocks: a view where one
        # piece covers it, else the one copy that joins the pieces,
        # straight into the caller's buffer where one was given.
        with ospan.span("engine.join") as sp:
            blocks = None
            if nb:
                # A block is the first BLOCK_SIZE bytes of its K rows:
                # all of them where K divides it (the whole full-block
                # range is then one contiguous piece), else without the
                # zero pad that ends the last row.
                blocks = y.reshape(nb, k * shard_size)[:, :BLOCK_SIZE]
            res, joined = _join_range(blocks, tail_block, lo, length, dst)
            # `y` goes back to the pool with this frame where the join
            # copied out of it; where `res` is a view of it, `res` is
            # what keeps its arena leased until the last view dies.
            if degraded:
                DATA_PATH.record_degraded_read(length,
                                               time.monotonic() - t_deg)
            sp.tag(bytes=joined, pieces=int(has_tail) + (
                nb if BLOCK_SIZE % k else min(nb, 1)))
        return res

    def _hash_shard_frames(self, bufs: list, nb: int, shard_size: int,
                           hs: int, algo: str) -> list[np.ndarray]:
        """Per-shard frame digests for the verify-only fast path.

        bufs[s] holds shard s's nb frames of (hs | shard_size).
        HighwayHash goes through the strided native kernel (digesting
        the frame data regions in place, no gather copy); other host
        algorithms hash via the batch hasher.  On multi-core hosts each
        shard is one worker-pool task — the native hash releases the
        GIL, so k shards verify concurrently; the 1-core bench host
        keeps the serial policy every other fan-out uses."""
        frame = hs + shard_size

        if algo.startswith("highwayhash") and bitrot_io._hh_native():
            from native.hh_native import hh256_frames_native

            def one(buf):
                return hh256_frames_native(buf, nb, frame, hs,
                                           shard_size)
        else:
            def one(buf):
                rows = np.ascontiguousarray(
                    np.frombuffer(buf, dtype=np.uint8).reshape(
                        nb, frame)[:, hs:])
                return bitrot_io.hash_rows(rows, algo)
        if self._serial_local() or self._on_drive_pool():
            return [one(b) for b in bufs]
        return list(self.pool.map(one, bufs))

    @staticmethod
    def _range_geometry(fi, part_size: int, b0: int, b1: int) -> dict:
        k = fi.erasure.data_blocks
        n_full_blocks = part_size // BLOCK_SIZE
        tail_len = part_size % BLOCK_SIZE
        tail_shard = -(-tail_len // k) if tail_len else 0
        has_tail = b1 > n_full_blocks
        nb_full = min(b1, n_full_blocks) - b0
        return {"nb_full": nb_full, "has_tail": has_tail,
                "tail_len": tail_len, "tail_shard": tail_shard,
                "expect": nb_full * fi.erasure.shard_size
                          + (tail_shard if has_tail else 0)}

    def _parse_shard_segment(self, raw: bytes, fi, geo: dict) -> np.ndarray:
        """Unframe + bitrot-verify one shard's frame range; enforce the
        exact expected logical length (short/corrupt => ErrFileCorrupt)."""
        row = bitrot_io.unframe_shard(raw, fi.erasure.shard_size,
                                      verify=True,
                                      algo=fi.erasure.bitrot_algo())
        if row.size != geo["expect"]:
            raise ErrFileCorrupt(
                f"shard segment {row.size} != expected {geo['expect']}")
        return row

    def _decode_shard_files(self, shard_bytes, fi, part_size) -> bytes:
        """Whole-object decode from full framed shard files (inline path):
        parse+verify what's present, then assemble."""
        k, m = fi.erasure.data_blocks, fi.erasure.parity_blocks
        b1 = -(-part_size // BLOCK_SIZE)
        geo = self._range_geometry(fi, part_size, 0, b1)
        rows: list[np.ndarray | None] = [None] * (k + m)
        for s, data in enumerate(shard_bytes):
            if data is None:
                continue
            try:
                rows[s] = self._parse_shard_segment(data, fi, geo)
            except ErrFileCorrupt:
                rows[s] = None
        return self._assemble(rows, fi, part_size, 0, 0, part_size)

    def _assemble(self, rows, fi, part_size, b0=0, offset=0,
                  length=None) -> bytes:
        """Reconstruct missing rows (device batched matmul) and assemble
        the requested byte range from verified shard segments."""
        k, m = fi.erasure.data_blocks, fi.erasure.parity_blocks
        shard_size = fi.erasure.shard_size
        if length is None:
            length = part_size - offset
        b1 = -(-(offset + length) // BLOCK_SIZE)
        geo = self._range_geometry(fi, part_size, b0, b1)
        nb_full, has_tail = geo["nb_full"], geo["has_tail"]
        tail_len, tail_shard = geo["tail_len"], geo["tail_shard"]

        if sum(1 for r in rows if r is not None) < k:
            raise ErrErasureReadQuorum("too many missing/corrupt shards")

        # Split rows into the full-block matrix and the tail segment.
        full_mat: list[np.ndarray | None] = [None] * (k + m)
        tails: list[np.ndarray | None] = [None] * (k + m)
        expect_full = nb_full * shard_size
        for s, r in enumerate(rows):
            if r is None:
                continue
            full_mat[s] = r[:expect_full].reshape(nb_full, shard_size) \
                if nb_full else np.zeros((0, shard_size), np.uint8)
            tails[s] = r[expect_full:] if has_tail else None

        # Reconstruct missing data rows (device batched matmul).
        missing = [s for s in range(k) if full_mat[s] is None]
        if missing and nb_full:
            avail = [s for s in range(k + m) if full_mat[s] is not None][:k]
            x = np.stack([full_mat[s] for s in avail], axis=1)  # (B, K, S)
            out = self.math.transform(k, m, x, tuple(avail),
                                      tuple(missing))
            for j, s in enumerate(missing):
                full_mat[s] = out[:, j, :]
        if has_tail:
            t_missing = [s for s in range(k) if tails[s] is None]
            if t_missing:
                t_avail = [s for s in range(k + m) if tails[s] is not None]
                cpu = self.math.cpu(k, m)
                shards_in = [tails[s] if s in t_avail else None
                             for s in range(k + m)]
                rec = cpu.reconstruct(shards_in, data_only=True)
                for s in t_missing:
                    tails[s] = rec[s]

        # Assemble: per block, concat K data segments, trim to block len.
        pieces = []
        for bi in range(nb_full):
            block = np.concatenate([full_mat[s][bi] for s in range(k)])
            pieces.append(block[:BLOCK_SIZE])
        if has_tail:
            tail_block = np.concatenate([tails[s] for s in range(k)])
            pieces.append(tail_block[:tail_len])
        data = np.concatenate(pieces) if pieces else np.zeros(0, np.uint8)
        lo = offset - b0 * BLOCK_SIZE
        return data[lo:lo + length].tobytes()

    # -- head / delete -------------------------------------------------------

    def update_object_metadata(self, bucket: str, obj: str,
                               fi: FileInfo) -> None:
        """Merge fi.metadata onto every drive's OWN copy of the
        version (updateObjectMetadata, cmd/erasure-object.go:1513).

        Each drive's xl.meta carries that drive's erasure index and —
        for small objects — that drive's inline SHARD; writing one
        drive's FileInfo to all of them would overwrite every inline
        shard with the same bytes and destroy the stripe. So the
        update is per drive: read its own version, replace only the
        metadata, write back."""
        def upd(d):
            own = d.read_version(bucket, obj, fi.version_id,
                                 read_data=True)
            own.metadata = dict(fi.metadata)
            d.update_metadata(bucket, obj, own)
        res = self._map_drives(upd)
        # Same write quorum every other mutation enforces: a stamp
        # landing on a minority would lose the quorum-merged read
        # election while reading as acknowledged.
        ok = sum(1 for _, e in res if e is None)
        if ok < self.n // 2 + 1:
            errs = [e for _, e in res if e is not None]
            raise errs[0] if errs else ErrObjectNotFound(
                f"{bucket}/{obj}")
        # The stamp changed the served metadata: cached FileInfos (and
        # hot-tier entries, which carry the FileInfo) are now stale.
        self._mark_dirty(bucket)

    def head_object(self, bucket: str, obj: str,
                    version_id: str = "") -> FileInfo:
        # Hot-tier metadata hit: a fresh-generation entry proves the
        # version is current (every mutation bumps the bucket
        # generation), so HEAD skips the drive stat fan-out.
        if self.hot_tier is not None and self.hot_tier.enabled:
            hfi = self.hot_tier.lookup_meta(bucket, obj, version_id)
            if hfi is not None:
                return hfi
        # HEAD always stats (a peer's write must be visible immediately)
        # but WRITES THROUGH the FileInfo cache: the common HEAD-then-GET
        # of one server request elects xl.meta once.
        entry = self._read_metadata(bucket, obj, version_id)
        self._fi_cache_store(bucket, obj, version_id, entry)
        fi = entry[0]
        if fi.deleted and not version_id:
            raise ErrObjectNotFound(f"{bucket}/{obj} (delete marker)")
        return fi

    def delete_object(self, bucket: str, obj: str, version_id: str = "",
                      versioned: bool = False) -> FileInfo | None:
        """Delete a version, or write a delete marker when the bucket is
        versioned and no explicit version was named
        (cf. DeleteObject, /root/reference/cmd/erasure-object.go:1038)."""
        if not self.bucket_exists(bucket):
            raise ErrBucketNotFound(bucket)
        with self.nslock.write_locked(bucket, obj):
            return self._delete_object_locked(bucket, obj, version_id,
                                              versioned)

    def _delete_object_locked(self, bucket, obj, version_id="",
                              versioned=False) -> FileInfo | None:
        write_quorum = self.n // 2 + 1
        if versioned and version_id == "":
            dm = FileInfo(volume=bucket, name=obj, version_id=new_uuid(),
                          mod_time_ns=_now_ns(), deleted=True)

            def mark(d):
                try:
                    d.delete_version(bucket, obj, mark_delete=True, fi=dm)
                except ErrFileNotFound:
                    # Delete marker on a nonexistent object is still legal.
                    d.write_metadata(bucket, obj, dm)

            res = self._map_drives(mark)
            err = Q.reduce_write_quorum_errs([e for _, e in res],
                                             write_quorum)
            if err is not None:
                raise err
            self._mark_dirty(bucket)
            return dm

        vid = normalize_version_id(version_id)
        res = self._map_drives(lambda d: d.delete_version(bucket, obj, vid))
        errs = [e for _, e in res]
        nf = (ErrFileNotFound, ErrFileVersionNotFound)
        if errs and all(isinstance(e, nf) for e in errs):
            if any(isinstance(e, ErrFileVersionNotFound) for e in errs):
                raise ErrVersionNotFound(f"{bucket}/{obj}@{version_id}")
            raise ErrObjectNotFound(f"{bucket}/{obj}")
        # A drive that never had the version counts as success.
        errs = [None if isinstance(e, nf) else e for e in errs]
        err = Q.reduce_write_quorum_errs(errs, write_quorum)
        if err is not None:
            raise err
        self._mark_dirty(bucket)
        return None

    # -- listing (walk-based; metacache comes later) -------------------------

    def list_objects(self, bucket: str, prefix: str = "",
                     max_keys: int = 10000,
                     marker: str = "") -> list[FileInfo]:
        """Quorum-merged listing through the metacache: the parallel
        drive walk + per-object quorum election runs once and is cached
        (memory + persisted) until a write to the bucket invalidates it
        (cf. /root/reference/cmd/metacache-server-pool.go:59)."""
        if not self.bucket_exists(bucket):
            raise ErrBucketNotFound(bucket)
        return self.metacache.list(bucket, prefix, marker, max_keys)

    def list_object_names(self, bucket: str,
                          prefix: str = "") -> list[str]:
        """All object names with ANY version present (delete-marked
        included) — the versions-listing walk needs names the
        latest-version listing filters out."""
        names: set[str] = set()
        res = self._map_drives(
            lambda d: [n for n, _ in d.walk_dir(bucket, prefix)])
        for entries, e in res:
            if e is None:
                names.update(entries)
        return sorted(names)

    def list_object_versions(self, bucket: str, obj: str) -> list[FileInfo]:
        """Quorum-elected version history: every drive's xl.meta is
        read and each version must be agreed on by a majority of the
        responding drives — a stale drive must not serve a stale (or
        resurrect a deleted) version history (cf. readAllFileInfo +
        findFileInfoInQuorum, cmd/erasure-metadata-utils.go)."""
        res = self._map_drives(
            lambda d: d.read_all(bucket, f"{obj}/xl.meta"))
        lists: list[list[FileInfo]] = []
        for raw, err in res:
            if err is not None or raw is None:
                continue
            try:
                lists.append(
                    XLMeta.from_bytes(raw).list_versions(bucket, obj))
            except StorageError:
                continue
        if not lists:
            # legacy xl.json objects: one unversioned entry per drive
            from ..storage import xlmeta_v1
            res = self._map_drives(
                lambda d: d.read_all(bucket,
                                     f"{obj}/{xlmeta_v1.XL_JSON}"))
            for raw, err in res:
                if err is not None or raw is None:
                    continue
                try:
                    fi = xlmeta_v1.parse_xl_json(raw, bucket, obj)
                    fi.is_latest = True
                    lists.append([fi])
                except StorageError:
                    continue
        if not lists:
            raise ErrObjectNotFound(f"{bucket}/{obj}")
        counts: dict[tuple, int] = {}
        keep: dict[tuple, FileInfo] = {}
        for lst in lists:
            for fi in lst:
                key = (fi.version_id, fi.mod_time_ns, fi.data_dir,
                       fi.size, fi.deleted, fi.metadata.get("etag", ""))
                counts[key] = counts.get(key, 0) + 1
                keep.setdefault(key, fi)
        # Read quorum = the erasure geometry's data_blocks, taken from
        # the LATEST erasure-bearing version and applied to every
        # version — matching objectQuorumFromMeta
        # (cf. /root/reference/cmd/erasure-metadata.go:389-417, which
        # derives ONE read quorum from the latest FileInfo; the k==m
        # "+1" there applies to WRITE quorum only). A version readable
        # at k shards must stay listable with only k metadata copies
        # reachable — lifecycle/replication iterating versions must
        # not skip durable objects. Objects with no erasure-bearing
        # version (pure delete-marker history) fall back to a simple
        # majority.
        # ... but only a latest FileInfo that is ITSELF present on at
        # least half the drives may set the quorum (getLatestFileInfo,
        # cmd/erasure-healing-common.go:196) — unquorate metadata from
        # one stale/corrupt drive must not become its own majority.
        quorum = self.n // 2 + 1
        trust_floor = max(self.n // 2, 1)
        for key, fi in sorted(keep.items(),
                              key=lambda kv: -kv[1].mod_time_ns):
            if fi.erasure is not None and counts[key] >= trust_floor:
                quorum = fi.erasure.data_blocks
                break
        if len(lists) < quorum:
            raise ErrErasureReadQuorum(
                f"{bucket}/{obj}: {len(lists)}/{self.n} version lists")
        out = [keep[k] for k, c in counts.items() if c >= quorum]
        if not out:
            raise ErrObjectNotFound(f"{bucket}/{obj} (no version in "
                                    "quorum)")
        out.sort(key=lambda fi: (-fi.mod_time_ns, fi.version_id))
        return out

    # -- internals -----------------------------------------------------------

    def _cleanup_tmp(self, tmp_id: str) -> None:
        def rm(d):
            d.delete(SYS_VOL, f"{TMP_DIR}/{tmp_id}", recursive=True)
        self._map_drives(rm)

    def _undo_publish(self, bucket, obj, fi, errs) -> None:
        """Roll back a publish fan-out that missed write quorum: drives
        that already renamed the version in must not keep it, or a
        REJECTED PUT becomes readable whenever the successes still
        reach READ quorum (read < write).  Best-effort — a drive that
        also fails the undo is left for dangling-object cleanup."""
        def undo(pos):
            if errs[pos] is not None or self.drives[pos] is None:
                return
            try:
                self.drives[pos].delete_version(bucket, obj,
                                                fi.version_id)
            except StorageError:
                pass
        self._map_drives_positions(undo)
