"""Multipart uploads: each part an independent erasure-coded stream.

The erasure-multipart equivalent (/root/reference/cmd/erasure-multipart.go:
NewMultipartUpload :39, PutObjectPart :400, CompleteMultipartUpload :771):
uploads stage under the reserved system volume, each part is encoded with
the SAME stripe geometry chosen at upload creation (so a 5 TiB object is
10,000 independent device-batched EC streams), and completion atomically
publishes all parts as one version via rename_data.

S3 semantics preserved: out-of-order part uploads, part overwrite
(last-write-wins), multipart ETag = md5(concat(part md5s))-N, minimum part
size for all but the last part.
"""

from __future__ import annotations

import hashlib
import time
import uuid

from ..observe import span as ospan
from ..observe.metrics import DATA_PATH
from ..parallel import pipeline as pl
from ..storage import bitrot_io
from ..storage.drive import MULTIPART_DIR, SYS_VOL, TMP_DIR
from ..storage.errors import (ErrErasureWriteQuorum, ErrFileNotFound,
                              ErrPathNotFound, StorageError)
from ..storage.xlmeta import (ErasureInfo, FileInfo, ObjectPartInfo,
                              XLMeta, new_uuid)
from ..utils import msgpackx, streams
from ..utils.crashpoints import crash_point
from . import quorum as Q
from .erasure_set import BATCH_BLOCKS, BLOCK_SIZE, ErasureSet

MIN_PART_SIZE = 5 * 1024 * 1024        # S3 minimum for all but the last part
MAX_PARTS = 10_000                     # docs/minio-limits.md:24-29

# Upload metadata keys (internal).
_MP_OBJECT_KEY = "x-mtpu-internal-mp-object"
_MP_BUCKET_KEY = "x-mtpu-internal-mp-bucket"


class ErrInvalidPart(StorageError):
    pass


class ErrInvalidPartOrder(StorageError):
    pass


class ErrPartTooSmall(StorageError):
    pass


class ErrUploadNotFound(StorageError):
    pass


def _upload_root(bucket: str, obj: str) -> str:
    h = hashlib.sha256(f"{bucket}/{obj}".encode()).hexdigest()[:32]
    return f"{MULTIPART_DIR}/{h}"


def _upload_path(bucket: str, obj: str, upload_id: str) -> str:
    return f"{_upload_root(bucket, obj)}/{upload_id}"


def new_multipart_upload(es: ErasureSet, bucket: str, obj: str, *,
                         metadata: dict | None = None,
                         parity: int | None = None) -> str:
    """Create an upload: fix the stripe geometry now so every part encodes
    identically (cf. newMultipartUpload, erasure-multipart.go:39)."""
    from ..storage.errors import ErrBucketNotFound
    if not es.bucket_exists(bucket):
        raise ErrBucketNotFound(bucket)
    parity = es.clamp_parity(parity)
    offline = sum(1 for d in es.drives if d is None)
    if offline and parity < es.n // 2:
        parity = min(parity + offline, es.n // 2)
    k = es.n - parity
    distribution = Q.hash_order(f"{bucket}/{obj}", es.n)
    upload_id = f"{new_uuid()}x{time.time_ns()}"
    meta = dict(metadata or {})
    meta[_MP_OBJECT_KEY] = obj
    meta[_MP_BUCKET_KEY] = bucket
    path = _upload_path(bucket, obj, upload_id)

    def write_one(pos):
        d = es.drives[pos]
        if d is None:
            raise ErrFileNotFound("offline")
        ec = ErasureInfo(data_blocks=k, parity_blocks=parity,
                         block_size=BLOCK_SIZE,
                         index=distribution[pos], distribution=distribution,
                         checksums=[])
        fi = FileInfo(volume=SYS_VOL, name=path, mod_time_ns=time.time_ns(),
                      metadata=meta, erasure=ec)
        d.write_metadata(SYS_VOL, path, fi)

    res = es._map_drives_positions(write_one)
    err = Q.reduce_write_quorum_errs([e for _, e in res], es.n // 2 + 1)
    if err is not None:
        raise err
    return upload_id


def _read_upload_fi(es: ErasureSet, bucket: str, obj: str,
                    upload_id: str) -> FileInfo:
    path = _upload_path(bucket, obj, upload_id)
    res = es._map_drives(lambda d: d.read_version(SYS_VOL, path))
    metas = [m for m, _ in res]
    n_found = sum(1 for m in metas if m is not None)
    if n_found < es._live_quorum():
        raise ErrUploadNotFound(f"{bucket}/{obj}: {upload_id}")
    return next(m for m in metas if m is not None)


def _part_meta_blob(part_number: int, etag: str, total: int,
                    algo: str) -> bytes:
    return msgpackx.packb({
        "n": part_number, "etag": etag, "size": total,
        "as": total, "mt": time.time_ns(), "algo": algo})


def put_object_part(es: ErasureSet, bucket: str, obj: str, upload_id: str,
                    part_number: int, data) -> ObjectPartInfo:
    """Encode one part as its own EC stream into the upload's staging dir
    (cf. PutObjectPart, erasure-multipart.go:400).  `data` is bytes or a
    reader — a reader streams through encode in O(batch) memory exactly
    like ErasureSet.put_object.

    The encode→write loop is a bounded StagePipeline: the shard appends
    of batch *i* run on the iter pool while batch *i+1* encodes on the
    caller's thread (the fused kernel and file IO both release the GIL,
    so the two stages genuinely overlap even on one core).  The encode
    is double-buffered so the in-flight batch survives the next fused
    put_frame.  Parts that fit one device batch skip staging-then-rename
    round trips: one encode, then a single per-drive fan-out that writes
    shard + rename + part meta together."""
    if not 1 <= part_number <= MAX_PARTS:
        raise ErrInvalidPart(f"part number {part_number}")
    fi = _read_upload_fi(es, bucket, obj, upload_id)
    ec = fi.erasure
    k, m = ec.data_blocks, ec.parity_blocks
    path = _upload_path(bucket, obj, upload_id)
    write_quorum = k + (1 if k == m else 0)

    stream = None
    if streams.is_reader(data):
        stream, data = data, b""

    # Stage under a unique name then rename into place, so a concurrent
    # re-upload of the same part can't interleave appends.
    stage = f"{path}/stage-{uuid.uuid4().hex}.{part_number}"
    algo = bitrot_io.write_algo()

    if stream is None and 0 < len(data) <= BATCH_BLOCKS * BLOCK_SIZE:
        # Small-part fast path (covers every trailing part of a large
        # upload): ONE device/native dispatch encodes the whole part,
        # then ONE fan-out per drive does shard write + publish rename +
        # part meta — instead of the streaming path's three rounds
        # (append, rename, meta) per drive.
        t0 = time.perf_counter()
        total = len(data)
        with ospan.span("mp.encode"):
            # ETag digest overlaps the encode dispatch (same bytes,
            # same order: byte-identical to hashlib.md5(data)).
            etag_md5 = streams.PipelinedMD5()
            with ospan.span("mp.md5"):
                etag_md5.feed(data)
            try:
                per_drive = Q.unshuffle_to_drives(
                    es._encode_full(bytes(data), k, m, algo),
                    ec.distribution)
            finally:
                etag_md5.close()
            with ospan.span("mp.md5"):
                etag = etag_md5.hexdigest()
            part_meta = _part_meta_blob(part_number, etag, total, algo)
        t1 = time.perf_counter()

        def put_one(pos):
            d = es.drives[pos]
            if d is None:
                raise ErrFileNotFound("offline")
            d.create_file(SYS_VOL, stage, per_drive[pos])
            d.rename_file(SYS_VOL, stage, SYS_VOL,
                          f"{path}/part.{part_number}")
            d.write_all(SYS_VOL, f"{path}/part.{part_number}.meta",
                        part_meta)

        with ospan.span("mp.write"):
            try:
                res = es._map_drives_positions(put_one)
                err = Q.reduce_write_quorum_errs([e for _, e in res],
                                                 write_quorum)
                if err is not None:
                    raise err
                crash_point("mp.part.post_publish")
            finally:
                _cleanup_stage(es, stage)
        t2 = time.perf_counter()
        DATA_PATH.record_mp_batch(total, t1 - t0, t2 - t1)
        return ObjectPartInfo(number=part_number, size=total,
                              actual_size=total, etag=etag)

    failed = [d is None for d in es.drives]
    md5 = streams.PipelinedMD5()
    total = 0

    def counted_chunks():
        nonlocal total
        for chunk, is_last in streams.batched_chunks(
                data, stream, BATCH_BLOCKS * BLOCK_SIZE, digest=md5):
            with ospan.span("mp.md5"):
                md5.update(chunk)
            total += len(chunk)
            yield chunk, is_last

    def shuffle(batch_shards):
        with ospan.span("engine.shuffle"):
            return Q.unshuffle_to_drives(batch_shards, ec.distribution)

    def write_batch(per_drive):
        def write_one(pos):
            d = es.drives[pos]
            if d is None or failed[pos]:
                return
            d.append_file(SYS_VOL, stage, per_drive[pos])

        for pos, (_, e) in enumerate(
                es._map_drives_positions(write_one)):
            if e is not None:
                failed[pos] = True
        if sum(1 for f in failed if not f) < write_quorum:
            raise ErrErasureWriteQuorum(
                f"{sum(1 for f in failed if not f)} < {write_quorum}")

    seen = [0]

    def record(read_s, compute_s, write_s):
        nbytes, seen[0] = total - seen[0], total
        DATA_PATH.record_mp_batch(nbytes, read_s + compute_s, write_s)

    try:
        # Encode of batch i+1 (the `reads` pull) overlaps the shard
        # appends of batch i (one write in flight keeps per-drive
        # append order).  double_buffer: the async batch must survive
        # the next fused put_frame's reuse of its thread's framing
        # buffer (the other planes alternate between two always).
        pl.StagePipeline(es._iter_pool).run(
            es._encode_chunks(counted_chunks(), k, m, algo,
                              double_buffer=True),
            shuffle, write_batch, on_batch=record,
            stages=("mp.encode", "mp.write"))

        with ospan.span("mp.md5"):
            etag = md5.hexdigest()
        part_meta = _part_meta_blob(part_number, etag, total, algo)

        def publish(pos):
            d = es.drives[pos]
            if d is None or failed[pos]:
                raise ErrFileNotFound("offline/failed")
            if total == 0:
                d.create_file(SYS_VOL, f"{path}/part.{part_number}", b"")
            else:
                d.rename_file(SYS_VOL, stage, SYS_VOL,
                              f"{path}/part.{part_number}")
            d.write_all(SYS_VOL, f"{path}/part.{part_number}.meta",
                        part_meta)

        with ospan.span("mp.publish"):
            res = es._map_drives_positions(publish)
        err = Q.reduce_write_quorum_errs([e for _, e in res],
                                         write_quorum)
        if err is not None:
            raise err
        crash_point("mp.part.post_publish")
    finally:
        md5.close()
        _cleanup_stage(es, stage)
    return ObjectPartInfo(number=part_number, size=total,
                          actual_size=total, etag=etag)


def _cleanup_stage(es: ErasureSet, stage: str) -> None:
    def rm(d):
        try:
            d.delete(SYS_VOL, stage)
        except StorageError:
            pass
    es._map_drives(rm)


def list_parts(es: ErasureSet, bucket: str, obj: str,
               upload_id: str) -> list[ObjectPartInfo]:
    """Quorum-agreed part list (cf. ListObjectParts)."""
    parts, _ = _list_parts_with_algos(es, bucket, obj, upload_id)
    return parts


def _list_parts_with_algos(es: ErasureSet, bucket: str, obj: str,
                           upload_id: str):
    """Part list + per-part bitrot algo map from the part metas."""
    _read_upload_fi(es, bucket, obj, upload_id)  # validates upload
    path = _upload_path(bucket, obj, upload_id)

    def scan(d) -> list[tuple]:
        keys = []
        try:
            names = d.list_raw(SYS_VOL, path)
        except StorageError:
            return keys
        for name in names:
            if not name.endswith(".meta") or not name.startswith("part."):
                continue
            try:
                pm = msgpackx.unpackb(d.read_all(SYS_VOL, f"{path}/{name}"))
            except StorageError:
                continue
            keys.append((pm["n"], pm["etag"], pm["size"], pm["as"],
                         pm.get("algo", "highwayhash256S")))
        return keys

    # One listing + meta-read sweep per drive, fanned out on the pool
    # (each sweep is a burst of small GIL-releasing syscalls).
    votes: dict[tuple, int] = {}
    for keys, _ in es._map_drives(scan):
        for key in keys or ():
            votes[key] = votes.get(key, 0) + 1
    quorum = es._live_quorum()
    best: dict[int, tuple] = {}
    for key, count in votes.items():
        if count >= quorum:
            n = key[0]
            if n not in best or votes[best[n]] < count:
                best[n] = key
    parts = [ObjectPartInfo(number=n, size=key[2], actual_size=key[3],
                            etag=key[1])
             for n, key in sorted(best.items())]
    algos = {n: key[4] for n, key in best.items()}
    return parts, algos


def upload_metadata(es: ErasureSet, bucket: str, obj: str,
                    upload_id: str) -> dict:
    """Client metadata an upload was created with (internal staging
    keys stripped) — what a relocated upload must be re-created with."""
    fi = _read_upload_fi(es, bucket, obj, upload_id)
    return {k: v for k, v in fi.metadata.items()
            if not k.startswith("x-mtpu-internal-mp-")}


def read_part_bytes(es: ErasureSet, bucket: str, obj: str,
                    upload_id: str, part_number: int) -> bytes:
    """Decode one STAGED part back to plaintext — the decommission
    mover's relocation read.  Staged parts are ordinary EC shard
    streams under the system volume, so the object read path decodes
    them once aimed at the staging layout: `_read_part` composes its
    path as `{name}/{data_dir}/part.{n}`, and name=<upload root>,
    data_dir=<upload id> lands exactly on `multipart/<hash>/<id>/part.n`."""
    fi_up = _read_upload_fi(es, bucket, obj, upload_id)
    ec = fi_up.erasure
    parts, algos = _list_parts_with_algos(es, bucket, obj, upload_id)
    info = next((p for p in parts if p.number == part_number), None)
    if info is None:
        raise ErrInvalidPart(f"part {part_number}")
    if info.size == 0:
        return b""
    # Client part numbers may be sparse; parts[] is indexed part_number-1
    # inside _read_part, so pad the synthetic list up to this part.
    pad = [ObjectPartInfo(number=i + 1, size=0, actual_size=0, etag="")
           for i in range(part_number - 1)]
    ec_read = ErasureInfo(
        data_blocks=ec.data_blocks, parity_blocks=ec.parity_blocks,
        block_size=ec.block_size, index=0,
        distribution=ec.distribution,
        checksums=[{"part": part_number,
                    "algo": algos.get(part_number, "highwayhash256S"),
                    "hash": b""}])
    fi = FileInfo(volume=SYS_VOL, name=_upload_root(bucket, obj),
                  data_dir=upload_id, size=info.size,
                  parts=pad + [info], erasure=ec_read)
    buf = bytearray(info.size)
    es._read_part(SYS_VOL, fi.name, fi, part_number, 0, info.size,
                  dst=memoryview(buf), healthy=False)
    return bytes(buf)


def abort_multipart_upload(es: ErasureSet, bucket: str, obj: str,
                           upload_id: str) -> None:
    # No _mark_dirty here on purpose: abort only deletes SYS_VOL
    # staging files — the object namespace never changed, so neither
    # the FileInfo cache nor the hot tier can hold anything stale
    # (complete_multipart_upload, which DOES publish, marks dirty).
    _read_upload_fi(es, bucket, obj, upload_id)  # 404 if unknown
    path = _upload_path(bucket, obj, upload_id)

    def rm(d):
        try:
            d.delete(SYS_VOL, path, recursive=True)
        except StorageError:
            pass
    es._map_drives(rm)


def list_multipart_uploads(es: ErasureSet, bucket: str,
                           prefix: str = "") -> list[dict]:
    """Active uploads for a bucket (cf. ListMultipartUploads)."""
    found: dict[str, dict] = {}
    for d in es.drives:
        if d is None:
            continue
        try:
            entries = list(d.walk_dir(SYS_VOL, MULTIPART_DIR + "/"))
        except StorageError:
            continue
        for rel, raw in entries:
            try:
                fi = XLMeta.from_bytes(raw).latest(SYS_VOL, rel)
            except StorageError:
                continue
            if fi.metadata.get(_MP_BUCKET_KEY) != bucket:
                continue
            o = fi.metadata.get(_MP_OBJECT_KEY, "")
            if prefix and not o.startswith(prefix):
                continue
            upload_id = rel.rsplit("/", 1)[-1]
            found.setdefault(upload_id, {
                "object": o, "upload_id": upload_id,
                "initiated_ns": fi.mod_time_ns})
    return sorted(found.values(), key=lambda u: (u["object"],
                                                 u["upload_id"]))


def complete_multipart_upload(es: ErasureSet, bucket: str, obj: str,
                              upload_id: str,
                              parts: list[tuple[int, str]], *,
                              versioned: bool = False) -> FileInfo:
    """Validate client part list, stitch staged parts into a fresh data
    dir, and publish one version atomically
    (cf. CompleteMultipartUpload, erasure-multipart.go:771)."""
    fi_up = _read_upload_fi(es, bucket, obj, upload_id)
    ec = fi_up.erasure
    listed, part_algos = _list_parts_with_algos(es, bucket, obj, upload_id)
    stored = {p.number: p for p in listed}
    if [n for n, _ in parts] != sorted({n for n, _ in parts}):
        raise ErrInvalidPartOrder("parts must be ascending and unique")

    chosen: list[ObjectPartInfo] = []
    for i, (n, etag) in enumerate(parts):
        p = stored.get(n)
        if p is None or p.etag != etag.strip('"'):
            raise ErrInvalidPart(f"part {n}")
        if p.size < MIN_PART_SIZE and i != len(parts) - 1:
            raise ErrPartTooSmall(
                f"part {n}: {p.size} < {MIN_PART_SIZE}")
        chosen.append(p)
    if not chosen:
        raise ErrInvalidPart("no parts")

    # S3 multipart ETag: md5 of the concatenated binary part md5s, -N.
    md5s = b"".join(bytes.fromhex(p.etag) for p in chosen)
    etag = f"{hashlib.md5(md5s).hexdigest()}-{len(chosen)}"
    total = sum(p.size for p in chosen)
    data_dir = new_uuid()
    version_id = new_uuid() if versioned else ""
    mod_time = time.time_ns()
    meta = {k: v for k, v in fi_up.metadata.items()
            if not k.startswith("x-mtpu-internal-mp-")}
    meta["etag"] = etag
    path = _upload_path(bucket, obj, upload_id)
    tmp_id = f"complete-{uuid.uuid4().hex}"
    k_, m_ = ec.data_blocks, ec.parity_blocks
    write_quorum = k_ + (1 if k_ == m_ else 0)

    def fi_for(pos: int) -> FileInfo:
        ec_pos = ErasureInfo(
            data_blocks=k_, parity_blocks=m_, block_size=BLOCK_SIZE,
            index=ec.distribution[pos], distribution=ec.distribution,
            checksums=[{"part": i + 1,
                        "algo": part_algos.get(p.number,
                                               "highwayhash256S"),
                        "hash": b""}
                       for i, p in enumerate(chosen)])
        return FileInfo(
            volume=bucket, name=obj, version_id=version_id,
            data_dir=data_dir, mod_time_ns=mod_time, size=total,
            metadata=meta,
            parts=[ObjectPartInfo(i + 1, p.size, p.actual_size, p.etag)
                   for i, p in enumerate(chosen)],
            erasure=ec_pos)

    def publish(pos):
        d = es.drives[pos]
        if d is None:
            raise ErrFileNotFound("offline")
        # Verify this drive actually has every chosen part — right shard
        # size AND the quorum-elected etag from the drive's own part meta.
        # Size alone is not enough: a drive that missed a same-size part
        # re-upload still holds the OLD content and would publish a torn
        # stripe whose bitrot frames are self-consistent (silent
        # corruption on reads that select this row).
        for p in chosen:
            logical = _shard_len(ec, p.size)
            want = bitrot_io.bitrot_shard_file_size(logical, ec.shard_size)
            if d.file_size(SYS_VOL, f"{path}/part.{p.number}") != want:
                raise ErrFileNotFound(f"part {p.number} incomplete here")
            try:
                pm = msgpackx.unpackb(
                    d.read_all(SYS_VOL, f"{path}/part.{p.number}.meta"))
            except StorageError:
                raise ErrFileNotFound(f"part {p.number} meta missing here") \
                    from None
            if pm.get("etag") != p.etag or pm.get("size") != p.size:
                raise ErrFileNotFound(f"part {p.number} stale here")
        # Renumber: client part numbers may be sparse; on disk the object
        # uses contiguous part.1..part.N.
        for i, p in enumerate(chosen):
            d.rename_file(SYS_VOL, f"{path}/part.{p.number}",
                          SYS_VOL, f"{TMP_DIR}/{tmp_id}/part.{i + 1}")
        crash_point("mp.complete.publish")
        d.rename_data(SYS_VOL, f"{TMP_DIR}/{tmp_id}", fi_for(pos),
                      bucket, obj)

    # The publish mutates the object namespace: hold the same write lock
    # as PUT/DELETE so a concurrent overwrite can't interleave per-drive
    # metadata writes (cf. NSLock in CompleteMultipartUpload,
    # erasure-multipart.go:771).  Each drive's publish is a chain of
    # stats + meta reads + renames — force the pool fan-out so the
    # per-drive chains assemble concurrently instead of serially, even
    # on the 1-core host (the work is syscalls, not Python).
    t0 = time.perf_counter()
    with es.nslock.write_locked(bucket, obj, timeout=30.0), \
            ospan.span("mp.publish"):
        res = es._map_drives_positions(publish, parallel=True)
    DATA_PATH.record_mp_complete(time.perf_counter() - t0)
    errs = [e for _, e in res]
    err = Q.reduce_write_quorum_errs(errs, write_quorum)
    if err is not None:
        # Roll back so the upload stays retryable (S3 allows retrying a
        # failed CompleteMultipartUpload): un-stage any parts parked in
        # tmp, drop the sub-quorum published version where publish
        # succeeded, and KEEP the upload dir.
        def rollback(pos):
            d = es.drives[pos]
            if d is None:
                return
            for i, p in enumerate(chosen):
                try:
                    d.rename_file(SYS_VOL, f"{TMP_DIR}/{tmp_id}/part.{i + 1}",
                                  SYS_VOL, f"{path}/part.{p.number}")
                except StorageError:
                    pass
            if errs[pos] is None:
                try:
                    d.delete_version(bucket, obj, version_id)
                except StorageError:
                    pass
            try:
                d.delete(SYS_VOL, f"{TMP_DIR}/{tmp_id}", recursive=True)
            except StorageError:
                pass
        es._map_drives_positions(rollback)
        raise err
    crash_point("mp.complete.post_publish")

    # Success: sweep staging + the whole upload dir.
    def rm(d):
        for p_ in (f"{TMP_DIR}/{tmp_id}", path):
            try:
                d.delete(SYS_VOL, p_, recursive=True)
            except StorageError:
                pass
    es._map_drives(rm)
    es._mark_dirty(bucket)
    return fi_for(0)


def _shard_len(ec: ErasureInfo, part_size: int) -> int:
    return ec.shard_file_size(part_size)
