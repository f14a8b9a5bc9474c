"""Heal subsystem: object heal, bucket heal, resumable drive heal.

The reference's healing stack rebuilt on the batched device codec:

- ``heal_object`` classifies every drive's copy of an object version
  (ok / offline / missing / outdated / corrupt), elects the latest
  quorum metadata, and reconstructs outdated drives with ONE batched
  verify+transform per batch of a part (the set's `math`,
  engine/shardmath.py) instead of the reference's streaming per-block
  pipe (cf. healObject,
  /root/reference/cmd/erasure-healing.go:244, and Erasure.Heal,
  /root/reference/cmd/erasure-lowlevel-heal.go:31).
- Dangling objects (provably unrecoverable) are purged
  (cf. isObjectDangling, /root/reference/cmd/erasure-healing.go:834).
- ``HealingTracker`` persists resumable per-drive healing progress on the
  drive being healed (cf. healingTracker / .healing.bin,
  /root/reference/cmd/background-newdisks-heal-ops.go:48).
- ``heal_drive`` walks the whole set onto one new/replaced drive
  (cf. healErasureSet, /root/reference/cmd/global-heal.go:166).
"""

from __future__ import annotations

import os
import threading
import time
import uuid
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

from ..observe import span as ospan
from ..observe.metrics import DATA_PATH
from ..parallel import pipeline as pl
from ..storage import bitrot_io
from ..storage.drive import SYS_VOL, TMP_DIR, LocalDrive
from ..storage.errors import (ErrErasureReadQuorum, ErrFileCorrupt,
                              ErrFileNotFound, ErrFileVersionNotFound,
                              ErrVolumeNotFound, StorageError)
from ..storage.xlmeta import FileInfo, XLMeta
from ..utils import msgpackx
from . import quorum as Q
from .erasure_set import BATCH_BLOCKS, BLOCK_SIZE, ErasureSet

# Drive states (cf. madmin drive states in the reference heal API).
DRIVE_OK = "ok"
DRIVE_OFFLINE = "offline"
DRIVE_MISSING = "missing"
DRIVE_OUTDATED = "outdated"
DRIVE_CORRUPT = "corrupt"

HEALING_FILE = "healing.bin"  # lives under <drive>/.mtpu.sys/


@dataclass
class HealResult:
    """Outcome of healing one object version (madmin.HealResultItem-like)."""
    bucket: str
    object: str
    version_id: str = ""
    size: int = 0
    before: list[str] = field(default_factory=list)
    after: list[str] = field(default_factory=list)
    healed_drives: list[int] = field(default_factory=list)
    purged: bool = False          # dangling object removed

    @property
    def healed(self) -> bool:
        return bool(self.healed_drives) or self.purged


def object_version_ids(es: ErasureSet, bucket: str, obj: str) -> list[str]:
    """Union of version ids seen on any drive (newest-first best effort)."""
    seen: dict[str, int] = {}
    res = es._map_drives(lambda d: d.read_all(bucket, f"{obj}/xl.meta"))
    for raw, e in res:
        if e is not None:
            continue
        try:
            meta = XLMeta.from_bytes(raw)
        except StorageError:
            continue
        for v in meta.versions:
            vid = v.get("id", "")
            seen[vid] = max(seen.get(vid, 0), v.get("mt", 0))
    return [vid for vid, _ in
            sorted(seen.items(), key=lambda kv: kv[1], reverse=True)]


def classify_drives(es: ErasureSet, bucket: str, obj: str, fi: FileInfo,
                    metas: list[FileInfo | None],
                    errs: list[Exception | None],
                    deep: bool = False) -> list[str]:
    """Per-drive-position state for one elected version.

    cf. shouldHealObjectOnDisk + disksWithAllParts,
    /root/reference/cmd/erasure-healing.go:206.
    """
    want_key = Q._fi_key(fi)
    states: list[str] = []
    for pos, d in enumerate(es.drives):
        if d is None:
            states.append(DRIVE_OFFLINE)
            continue
        meta = metas[pos]
        if meta is None:
            err = errs[pos]
            if isinstance(err, (ErrFileNotFound, ErrFileVersionNotFound,
                                ErrVolumeNotFound)):
                states.append(DRIVE_MISSING)
            elif isinstance(err, ErrFileCorrupt):
                states.append(DRIVE_CORRUPT)
            else:
                states.append(DRIVE_OFFLINE)
            continue
        if Q._fi_key(meta) != want_key:
            states.append(DRIVE_OUTDATED)
            continue
        states.append(_verify_drive_data(d, bucket, obj, fi, meta, deep))
    return states


def _verify_drive_data(d: LocalDrive, bucket: str, obj: str, fi: FileInfo,
                       meta: FileInfo, deep: bool) -> str:
    """Check this drive's shard data for the version: size always, full
    bitrot verify when deep (cf. VerifyFile server-side deep scan,
    /root/reference/cmd/xl-storage.go:2194)."""
    if fi.deleted:
        return DRIVE_OK
    if fi.inline_data is not None or not fi.data_dir:
        # Inline shard rides in xl.meta; deep-verify its frames.
        if deep and meta.inline_data is not None and fi.erasure is not None:
            try:
                bitrot_io.unframe_shard(meta.inline_data,
                                        fi.erasure.shard_size, verify=True,
                                        algo=fi.erasure.bitrot_algo())
            except StorageError:
                return DRIVE_CORRUPT
        if meta.inline_data is None:
            return DRIVE_CORRUPT
        return DRIVE_OK
    ec = fi.erasure
    for part in fi.parts:
        path = f"{obj}/{fi.data_dir}/part.{part.number}"
        logical = ec.shard_file_size(part.size)
        algo = ec.bitrot_algo(part.number)
        want = bitrot_io.bitrot_shard_file_size(logical, ec.shard_size,
                                                algo)
        try:
            if deep:
                d.verify_file(bucket, path, ec.shard_size, logical,
                              algo=algo)
            elif d.file_size(bucket, path) != want:
                return DRIVE_CORRUPT
        except ErrFileNotFound:
            return DRIVE_MISSING
        except StorageError:
            return DRIVE_CORRUPT
    return DRIVE_OK


def heal_object(es: ErasureSet, bucket: str, obj: str, version_id: str = "",
                deep: bool = False, dry_run: bool = False,
                remove_dangling: bool = True) -> list[HealResult]:
    """Heal one object: every version when version_id == "", else that one.

    Returns one HealResult per version examined.
    cf. healObject, /root/reference/cmd/erasure-healing.go:244.
    """
    if version_id:
        vids = [version_id]
    else:
        vids = object_version_ids(es, bucket, obj)
        if not vids:
            # No drive has any metadata: nothing to heal (or the object is
            # gone); mirror the reference's not-found no-op.
            return []
    # Heal mutates shard files + metadata: same write lock as PUT/DELETE
    # (cf. NSLock in healObject, cmd/erasure-healing.go:276).
    with es.nslock.write_locked(bucket, obj, timeout=30.0):
        results = [_heal_version(es, bucket, obj, vid, deep, dry_run,
                                 remove_dangling) for vid in vids]
        # Heal is a mutation like any other: promoted spares / purged
        # dangling versions change what a read elects, so the FileInfo
        # cache and hot tier must be invalidated (a missed bump here
        # would let the hot cache serve the pre-heal body forever).
        if not dry_run and any(r.healed_drives or r.purged
                               for r in results):
            es._mark_dirty(bucket)
        return results


def _heal_version(es: ErasureSet, bucket: str, obj: str, version_id: str,
                  deep: bool, dry_run: bool,
                  remove_dangling: bool) -> HealResult:
    res = es._map_drives(lambda d: d.read_version(bucket, obj, version_id))
    metas = [m for m, _ in res]
    errs = [e for _, e in res]
    result = HealResult(bucket=bucket, object=obj, version_id=version_id)

    n_found = sum(1 for m in metas if m is not None)
    read_quorum, write_quorum = Q.object_quorum_from_meta(
        metas, es.n, es.default_parity)
    try:
        fi = Q.find_file_info_in_quorum(metas, read_quorum) \
            if n_found else None
    except ErrErasureReadQuorum:
        fi = None

    if fi is None:
        # Sub-quorum metadata. Purge only when provably dangling: every
        # drive reported a definite answer (no offline/unknown that could
        # be hiding a copy) and still no quorum
        # (cf. isObjectDangling, erasure-healing.go:834).
        definite = all(
            d is None or m is not None or isinstance(
                e, (ErrFileNotFound, ErrFileVersionNotFound,
                    ErrVolumeNotFound, ErrFileCorrupt))
            for d, m, e in zip(es.drives, metas, errs))
        offline = sum(1 for d in es.drives if d is None)
        if remove_dangling and definite and n_found + offline < read_quorum:
            result.before = [DRIVE_OFFLINE if d is None else
                             (DRIVE_OK if m is not None else DRIVE_MISSING)
                             for d, m in zip(es.drives, metas)]
            if not dry_run:
                _purge_version(es, bucket, obj, version_id, metas)
            result.purged = True
            result.after = [DRIVE_OFFLINE if d is None else DRIVE_MISSING
                            for d in es.drives]
            return result
        raise ErrErasureReadQuorum(
            f"heal {bucket}/{obj}@{version_id}: "
            f"{n_found} metas < quorum {read_quorum}")

    result.version_id = fi.version_id
    result.size = fi.size
    states = classify_drives(es, bucket, obj, fi, metas, errs, deep)
    result.before = list(states)
    targets = [pos for pos, st in enumerate(states)
               if st in (DRIVE_MISSING, DRIVE_OUTDATED, DRIVE_CORRUPT)
               and es.drives[pos] is not None]
    if not targets:
        result.after = list(states)
        return result
    if dry_run:
        result.after = list(states)
        result.healed_drives = targets
        return result

    if fi.deleted or fi.inline_data is not None or not fi.data_dir:
        _heal_metadata_only(es, bucket, obj, fi, metas, states, targets)
    else:
        sources = [pos for pos, st in enumerate(states) if st == DRIVE_OK]
        k = fi.erasure.data_blocks
        if len(sources) < k:
            raise ErrErasureReadQuorum(
                f"heal {bucket}/{obj}: only {len(sources)} intact copies "
                f"< {k} needed")
        _heal_data(es, bucket, obj, fi, sources, targets)

    after = list(states)
    for pos in targets:
        after[pos] = DRIVE_OK
    result.after = after
    result.healed_drives = targets
    return result


def _purge_version(es: ErasureSet, bucket: str, obj: str, version_id: str,
                   metas: list[FileInfo | None]) -> None:
    """Remove a dangling version wherever it exists."""
    def rm(d):
        try:
            d.delete_version(bucket, obj, version_id)
        except (ErrFileNotFound, ErrFileVersionNotFound):
            pass
    es._map_drives(rm)


def _ensure_bucket_on(drive, bucket: str) -> None:
    """Heal explicitly recreates a missing bucket volume on its target
    drive — the data path itself refuses to resurrect volumes (a PUT
    racing a bucket delete must fail, drive._ensure_parent_in_vol), so
    only heal gets to bring the directory back (cf. healBucket before
    object heal, /root/reference/cmd/erasure-healing.go:281)."""
    from ..storage.errors import ErrVolumeExists
    try:
        drive.make_volume(bucket)
    except ErrVolumeExists:
        pass


def _heal_metadata_only(es, bucket, obj, fi: FileInfo, metas, states,
                        targets: list[int]) -> None:
    """Delete markers and inline objects: rewrite xl.meta on targets.

    The inline shard for a target drive is the shard its stripe position
    owns; reconstruct it from intact copies when the source lacks it."""
    if fi.deleted:
        for pos in targets:
            _ensure_bucket_on(es.drives[pos], bucket)
            es.drives[pos].write_metadata(bucket, obj, fi)
        return
    ec = fi.erasure
    dist = ec.distribution
    k, m = ec.data_blocks, ec.parity_blocks
    # Gather intact framed inline shards by shard index.
    shard_bytes: list[bytes | None] = [None] * (k + m)
    for pos, st in enumerate(states):
        meta = metas[pos]
        if st == DRIVE_OK and meta is not None and meta.inline_data is not None:
            shard_bytes[dist[pos] - 1] = meta.inline_data
    # Unframe + verify available shards to logical rows.
    logical = ec.shard_file_size(fi.size)
    rows: list[np.ndarray | None] = [None] * (k + m)
    for s, data in enumerate(shard_bytes):
        if data is None:
            continue
        try:
            row = bitrot_io.unframe_shard(data, ec.shard_size, verify=True,
                                          algo=ec.bitrot_algo())
            if row.size == logical:
                rows[s] = row
        except StorageError:
            continue
    need = sorted({dist[pos] - 1 for pos in targets
                   if rows[dist[pos] - 1] is None})
    if need:
        avail = [s for s in range(k + m) if rows[s] is not None]
        if len(avail) < k:
            raise ErrErasureReadQuorum(
                f"heal inline {bucket}/{obj}: {len(avail)} < {k}")
        rebuilt = _reconstruct_rows(es, fi, rows, avail, need)
        for s, row in zip(need, rebuilt):
            rows[s] = row
    for pos in targets:
        s = dist[pos] - 1
        framed = bitrot_io.frame_shard(rows[s], ec.shard_size,
                                       ec.bitrot_algo())
        fi_pos = _fi_for_drive(fi, pos, inline=framed)
        _ensure_bucket_on(es.drives[pos], bucket)
        es.drives[pos].write_metadata(bucket, obj, fi_pos)


def _fi_for_drive(fi: FileInfo, pos: int,
                  inline: bytes | None = None) -> FileInfo:
    """Per-drive FileInfo: erasure.index points at this drive's shard."""
    ec = fi.erasure
    from ..storage.xlmeta import ErasureInfo
    ec_pos = None
    if ec is not None:
        ec_pos = ErasureInfo(
            data_blocks=ec.data_blocks, parity_blocks=ec.parity_blocks,
            block_size=ec.block_size, index=ec.distribution[pos],
            distribution=list(ec.distribution), algorithm=ec.algorithm,
            checksums=list(ec.checksums))
    return FileInfo(
        volume=fi.volume, name=fi.name, version_id=fi.version_id,
        data_dir=fi.data_dir if inline is None else "",
        mod_time_ns=fi.mod_time_ns, size=fi.size, deleted=fi.deleted,
        metadata=dict(fi.metadata), parts=list(fi.parts), erasure=ec_pos,
        inline_data=inline)


def _reconstruct_rows(es: ErasureSet, fi: FileInfo,
                      rows: list[np.ndarray | None], avail: list[int],
                      need: list[int]) -> list[np.ndarray]:
    """Rebuild `need` shard rows (full logical shard-file contents) from K
    available rows — batched device matmul for the full blocks, CPU codec
    for the tail fragment (cf. Erasure.Heal decode->re-encode,
    /root/reference/cmd/erasure-lowlevel-heal.go:31)."""
    ec = fi.erasure
    k, m = ec.data_blocks, ec.parity_blocks
    shard_size = ec.shard_size
    logical = rows[avail[0]].size
    use = avail[:k]
    # Host fast path: RS is positional, so whole LOGICAL rows (full
    # blocks AND tail in one go) transform with per-row pointers — no
    # batch stacking, no per-block loop (native ec_gf_rows, GFNI when
    # the CPU has it).
    host = es.math.host_fused(k, m)
    if host is not None:
        return host.gf_transform_rows(
            [rows[s] for s in use], list(use), k, m, list(need))
    # Split logical shard into full-block matrix + tail.
    n_full = logical // shard_size
    tail_len = logical - n_full * shard_size
    out_rows = [np.zeros(logical, dtype=np.uint8) for _ in need]
    if n_full:
        x = np.stack([rows[s][:n_full * shard_size].reshape(n_full,
                                                            shard_size)
                      for s in use], axis=1)  # (B, K, S)
        y = es.math.transform(k, m, x, tuple(use), tuple(need))  # (B, T, S)
        for j in range(len(need)):
            out_rows[j][:n_full * shard_size] = y[:, j, :].reshape(-1)
    if tail_len:
        shards_in: list[np.ndarray | None] = [None] * (k + m)
        for s in avail:
            shards_in[s] = rows[s][n_full * shard_size:]
        full = es.math.cpu(k, m).reconstruct(shards_in)
        for j, s in enumerate(need):
            out_rows[j][n_full * shard_size:] = full[s]
    return out_rows


#: Blocks per reconstruct batch — one device dispatch / native C pass,
#: and the memory bound of the heal pipeline (O(batch), never O(part)).
HEAL_BATCH_BLOCKS = BATCH_BLOCKS


def _pipelined() -> bool:
    """Env escape hatch (MTPU_HEAL_PIPELINE=0): run the one-shot serial
    reference path. The equivalence test drives both implementations
    over the same corruption matrix and diffs the repaired bytes."""
    return os.environ.get("MTPU_HEAL_PIPELINE", "1") != "0"


def _heal_data(es: ErasureSet, bucket: str, obj: str, fi: FileInfo,
               sources: list[int], targets: list[int]) -> None:
    """Reconstruct every part's shard files onto the target drives and
    publish atomically via rename_data.

    Pipelined: surviving-shard reads fan out across drives, parts are
    staged in HEAL_BATCH_BLOCKS-deep batches through a double-buffered
    read -> verify+decode(+re-encode) -> write pipeline (the Erasure.Heal
    role, cmd/erasure-lowlevel-heal.go:31, on the PUT path's `pending`
    scheme), so drive I/O for batch i+1 overlaps the decode of batch i
    and the repaired-shard appends of batch i-1."""
    ec = fi.erasure
    dist = ec.distribution
    tmp_id = f"heal-{uuid.uuid4().hex}"
    need = sorted({dist[pos] - 1 for pos in targets})

    try:
        for part in fi.parts:
            if _pipelined():
                _heal_part_pipelined(es, bucket, obj, fi, part, sources,
                                     targets, need, tmp_id)
            else:
                _heal_part_serial(es, bucket, obj, fi, part, sources,
                                  targets, need, tmp_id)
        with ospan.span("heal.publish"):
            for pos in targets:
                fi_pos = _fi_for_drive(fi, pos)
                _ensure_bucket_on(es.drives[pos], bucket)
                es.drives[pos].rename_data(SYS_VOL, f"{TMP_DIR}/{tmp_id}",
                                           fi_pos, bucket, obj)
        DATA_PATH.record_heal_object()
    finally:
        for pos in targets:
            try:
                es.drives[pos].delete(SYS_VOL, f"{TMP_DIR}/{tmp_id}",
                                      recursive=True)
            except StorageError:
                pass


def _heal_part_serial(es: ErasureSet, bucket: str, obj: str, fi: FileInfo,
                      part, sources: list[int], targets: list[int],
                      need: list[int], tmp_id: str) -> None:
    """Reference implementation: whole-part staging, serial drive loop
    (the pre-pipeline path, kept as the equivalence oracle)."""
    ec = fi.erasure
    dist = ec.distribution
    k = ec.data_blocks
    path = f"{obj}/{fi.data_dir}/part.{part.number}"
    logical = ec.shard_file_size(part.size)
    rows: list[np.ndarray | None] = [None] * (k + ec.parity_blocks)
    got = 0
    # Read + verify source shards until K good ones (spares beyond
    # the first K cover sources that fail at read time).
    for pos in sources:
        if got >= k:
            break
        s = dist[pos] - 1
        try:
            d = es.drives[pos]
            # mmap on local drives: the fused unframe verifies
            # straight off the page cache (no read() copy).
            raw = (d.read_file_view(bucket, path)
                   if isinstance(d, LocalDrive)
                   else d.read_file(bucket, path))
            row = bitrot_io.unframe_shard(
                raw, ec.shard_size, verify=True,
                algo=ec.bitrot_algo(part.number))
            if row.size != logical:
                raise ErrFileCorrupt("short shard")
            rows[s] = row
            got += 1
        except StorageError:
            continue
    if got < k:
        raise ErrErasureReadQuorum(
            f"heal {bucket}/{obj} part {part.number}: "
            f"{got} readable < {k}")
    avail = [s for s in range(len(rows)) if rows[s] is not None]
    missing = [s for s in need if rows[s] is None]
    rebuilt = _reconstruct_rows(es, fi, rows, avail, missing) \
        if missing else []
    for s, row in zip(missing, rebuilt):
        rows[s] = row
    for pos in targets:
        s = dist[pos] - 1
        framed = bitrot_io.frame_shard(
            rows[s], ec.shard_size, ec.bitrot_algo(part.number))
        es.drives[pos].create_file(
            SYS_VOL, f"{TMP_DIR}/{tmp_id}/part.{part.number}",
            framed)


def _heal_part_pipelined(es: ErasureSet, bucket: str, obj: str,
                         fi: FileInfo, part, sources: list[int],
                         targets: list[int], need: list[int],
                         tmp_id: str) -> None:
    """Batched double-buffered reconstruct of one part onto the targets.

    Memory is O(batch): surviving shards are read as ranged frame
    segments (fanned out across drives), each HEAL_BATCH_BLOCKS batch is
    verified+decoded in one native/device pass (+re-encoded for parity
    targets), framed vectorized, and appended to the per-target staging
    files with one write in flight — so batch i+1's reads overlap batch
    i's decode and batch i-1's writes. A bitrot hit or read failure
    drops the source and promotes a spare for that batch onward, exactly
    like the GET path's spare-read policy."""
    from ..ops import devcache as devcache_mod
    ec = fi.erasure
    dist = ec.distribution
    k, m = ec.data_blocks, ec.parity_blocks
    S = ec.shard_size
    algo = ec.bitrot_algo(part.number)
    hs = bitrot_io.digest_size(algo)
    frame = hs + S
    logical = ec.shard_file_size(part.size)
    n_full = part.size // BLOCK_SIZE
    tail_shard = logical - n_full * S
    want = bitrot_io.bitrot_shard_file_size(logical, S, algo)
    path = f"{obj}/{fi.data_dir}/part.{part.number}"
    tmp_path = f"{TMP_DIR}/{tmp_id}/part.{part.number}"
    need_data = [s for s in need if s < k]
    need_parity = [s for s in need if s >= k]

    src_pos = {dist[pos] - 1: pos for pos in sources}
    candidates = sorted(src_pos)
    serial = es._serial_local()

    def quorum_err(got: int) -> ErrErasureReadQuorum:
        return ErrErasureReadQuorum(
            f"heal {bucket}/{obj} part {part.number}: "
            f"{got} readable < {k}")

    # Source election: a framed-size stat weeds out missing/truncated
    # shards before any data moves (fan-out: one stat per drive).
    def usable(s: int) -> bool:
        d = es.drives[src_pos[s]]
        try:
            return d is not None and d.file_size(bucket, path) == want
        except StorageError:
            return False

    if serial:
        good = [s for s in candidates if usable(s)]
    else:
        flags = list(es.pool.map(usable, candidates))
        good = [s for s, f in zip(candidates, flags) if f]
    if len(good) < k:
        raise quorum_err(len(good))
    sel = good[:k]          # kept sorted; mutated on bitrot/read failure
    spares = good[k:]

    fused_host = es.math.host_fused(k, m, algo)
    # Device-resident shard cache: a prior healthy GET's verified data
    # matrix can cover a heal batch — the rebuild then runs straight
    # off residency (host copy, or the already-placed device array):
    # zero re-reads of source shards, zero uploads.
    dcache = devcache_mod.get() if devcache_mod.enabled() else None

    def read_one(s: int, lo: int, ln: int) -> bytes:
        raw = es.drives[src_pos[s]].read_file(bucket, path, lo, ln)
        if len(raw) != ln:
            raise ErrFileCorrupt(
                f"short shard segment ({len(raw)} != {ln})")
        return raw

    def read_batch(batch):
        """Read stage: fan the selected sources' frame segments out
        across drives. Failures are left out — the compute stage drops
        the source and promotes a spare."""
        b0, nb = batch
        lo, ln = b0 * frame, nb * frame
        t0 = time.perf_counter()
        cur = list(sel)
        data: dict[int, bytes] = {}
        if serial:
            for s in cur:
                try:
                    data[s] = read_one(s, lo, ln)
                except StorageError:
                    pass
        else:
            futs = {s: es.pool.submit(read_one, s, lo, ln) for s in cur}
            for s, f in futs.items():
                try:
                    data[s] = f.result()
                except StorageError:
                    pass
        return batch, data, time.perf_counter() - t0

    def compute(item):
        """Verify + decode (+ re-encode parity) one batch; on a bad row,
        swap in a spare and rerun the batch."""
        (b0, nb), data, read_s = item
        lo, ln = b0 * frame, nb * frame
        t0 = time.perf_counter()
        if dcache is not None:
            found = dcache.lookup_range(
                es._devcache_owner, bucket, obj, part.number,
                fi.data_dir, algo, b0, b0 + nb)
            if found is not None:
                # The batch's verified systematic matrix is resident:
                # rebuild every target from it.  GF arithmetic is
                # exact, so the rebuilt rows are byte-identical to the
                # re-read path's (the cached bytes ARE the shards that
                # passed verify at fill time).
                e, boff = found
                y = e.host[boff:boff + nb]
                out = {}
                rebuilt = None
                if need:
                    xd = e.dev
                    rebuilt = es.math.transform(
                        k, m, y, tuple(range(k)), tuple(need),
                        None if xd is None else xd[boff:boff + nb], algo)
                for j, s in enumerate(need):
                    out[s] = rebuilt[:, j, :]
                stack = np.stack([out[s] for s in need])
                framed = bitrot_io.frame_shard_views(
                    None, None, None, algo, shards=stack)
                DATA_PATH.record_host_hash("heal", stack.nbytes)
                return ((b0, nb), dict(zip(need, framed)), read_s,
                        time.perf_counter() - t0)
        while True:
            # Reconcile with the current selection: a source dropped by
            # an earlier batch leaves a hole in this prefetched read; a
            # promoted spare has no bytes yet.
            for s in [s for s in sel if s not in data]:
                try:
                    data[s] = read_one(s, lo, ln)
                except StorageError:
                    sel.remove(s)
            while len(sel) < k:
                if not spares:
                    raise quorum_err(len(sel))
                s = spares.pop(0)
                try:
                    data[s] = read_one(s, lo, ln)
                except StorageError:
                    continue
                sel.append(s)
                sel.sort()
            cur = list(sel)
            out: dict[int, np.ndarray] = {}
            if fused_host is not None:
                # ONE C pass: digest every chosen row, gather the data
                # matrix, rebuild missing data rows. Parity targets
                # re-encode from the full matrix right after.
                dmiss = [s for s in range(k) if s not in cur]
                dtargets = dmiss if need_parity else \
                    [s for s in dmiss if s in need_data]
                y, okf, nbad = fused_host.get_verify(
                    [data[s] for s in cur], cur, nb, S, k, m, dtargets)
                DATA_PATH.record_host_hash("heal", nb * k * S)
                if nbad:
                    for j, s in enumerate(cur):
                        if not okf[j]:
                            sel.remove(s)
                            data.pop(s, None)
                    continue
                for s in need_data:
                    out[s] = y[:, s, :]
                if need_parity:
                    prows = np.asarray(es.math.native(k, m).transform_blocks(
                        y, tuple(range(k)), tuple(need_parity)))
                    for j, s in enumerate(need_parity):
                        out[s] = prows[:, j, :]
                break
            # Generic path: gather rows, digest-verify, then ONE
            # transform straight to every needed row — transform_matrix
            # maps any K sources to arbitrary targets, parity included.
            bufs = {s: np.frombuffer(data[s], dtype=np.uint8)
                    .reshape(nb, frame) for s in cur}
            x = np.empty((nb, k, S), dtype=np.uint8)
            for i, s in enumerate(cur):
                x[:, i, :] = bufs[s][:, hs:]
            # Heal shares the verify_and_transform queue with degraded
            # GETs (`ShardMath.verify_transform`).
            digests, rebuilt = es.math.verify_transform(
                x, k, m, tuple(cur), tuple(need), algo, site="heal")
            bad = [cur[i] for i in range(k)
                   if not np.array_equal(digests[:, i],
                                         bufs[cur[i]][:, :hs])]
            if bad:
                for s in bad:
                    sel.remove(s)
                    data.pop(s, None)
                continue
            for j, s in enumerate(need):
                out[s] = rebuilt[j]
            break
        # Vectorized framing of the rebuilt rows (same frame layout the
        # serial frame_shard produces, batch-concatenation identical).
        stack = np.stack([out[s] for s in need])         # (T, nb, S)
        framed = bitrot_io.frame_shard_views(None, None, None, algo,
                                             shards=stack)
        DATA_PATH.record_host_hash("heal", stack.nbytes)
        payload = dict(zip(need, framed))
        return (b0, nb), payload, read_s, time.perf_counter() - t0

    def write_batch(res):
        """Write stage: append the repaired frames to every target's
        staging file (fan-out across target drives)."""
        (b0, nb), payload, read_s, decode_s = res
        t0 = time.perf_counter()

        def put(pos):
            es.drives[pos].append_file(SYS_VOL, tmp_path,
                                       payload[dist[pos] - 1])
        if serial or len(targets) == 1:
            for pos in targets:
                put(pos)
        else:
            list(es.pool.map(put, targets))
        DATA_PATH.record_heal_batch(
            nb, HEAL_BATCH_BLOCKS, len(sel) * nb * frame,
            len(targets) * nb * frame, read_s, decode_s,
            time.perf_counter() - t0)

    batches = [(b0, min(HEAL_BATCH_BLOCKS, n_full - b0))
               for b0 in range(0, n_full, HEAL_BATCH_BLOCKS)]
    # The pipeline threads pay off even on the 1-core host: reads,
    # appends, and the native decode all release the GIL, so disk I/O
    # for neighboring batches genuinely overlaps the C pass.
    def bridge(read_s, compute_s, write_s):
        # Runs in the (possibly traced) caller thread — an
        # admin-triggered heal shows its stage times in the trace.
        ospan.record("heal.read", read_s)
        ospan.record("heal.decode", compute_s)
        ospan.record("heal.write", write_s)

    pl.StagePipeline(es._iter_pool).run(
        pl.prefetch_map(read_batch, batches, es._iter_pool, depth=1),
        compute, write_batch, on_batch=bridge)

    if tail_shard:
        # Tail fragment (one short frame per shard): CPU oracle codec,
        # same bytes as the serial whole-row path.
        lo, ln = n_full * frame, hs + tail_shard
        shards_in: list[np.ndarray | None] = [None] * (k + m)
        got = 0
        for s in list(sel) + spares:
            if got >= k:
                break
            try:
                row = bitrot_io.unframe_shard(
                    read_one(s, lo, ln), tail_shard, verify=True,
                    algo=algo)
                if row.size != tail_shard:
                    raise ErrFileCorrupt("short tail")
                shards_in[s] = row
                got += 1
            except StorageError:
                continue
        if got < k:
            raise quorum_err(got)
        if any(shards_in[s] is None for s in need):
            full = es.math.cpu(k, m).reconstruct(shards_in)
            for s in need:
                if shards_in[s] is None:
                    shards_in[s] = full[s]
        for pos in targets:
            es.drives[pos].append_file(
                SYS_VOL, tmp_path,
                bitrot_io.frame_shard(shards_in[dist[pos] - 1], S, algo))


def heal_format(es: ErasureSet) -> list[int]:
    """Restore format.json + the system volume on drives that lost
    them (wiped/replaced disk) — the HealFormat step that must precede
    bucket/object healing, because every write stages through the sys
    volume's tmp dir (cf. HealFormat, cmd/format-erasure.go:798).
    Returns healed positions."""
    from ..storage.format import load_format, new_format, save_format
    fmts: list[dict | None] = []
    for d in es.drives:
        if d is None:
            fmts.append(None)
            continue
        try:
            fmts.append(load_format(d))
        except StorageError:
            fmts.append(None)
    ref = next((f for f in fmts if f), None)
    if ref is None:
        return []
    layout = ref["xl"]["sets"]
    healed = []
    for pos, (d, f) in enumerate(zip(es.drives, fmts)):
        if d is None or f is not None:
            continue
        try:
            d.init_sys_volume()
            save_format(d, new_format(ref["id"], layout,
                                      layout[es.set_index][pos]))
            healed.append(pos)
        except StorageError:
            continue
    return healed


def heal_bucket(es: ErasureSet, bucket: str) -> list[int]:
    """Create the bucket volume on drives missing it; returns healed
    positions (cf. HealBucket, /root/reference/cmd/erasure-bucket.go)."""
    res = es._map_drives(lambda d: d.stat_volume(bucket))
    present = sum(1 for _, e in res if e is None)
    if present < es._live_quorum():
        raise ErrVolumeNotFound(bucket)
    healed = []
    for pos, (_, e) in enumerate(res):
        if e is not None and es.drives[pos] is not None:
            try:
                es.drives[pos].make_volume(bucket)
                healed.append(pos)
            except StorageError:
                pass
    return healed


# ---------------------------------------------------------------------------
# Resumable drive healing (new/replaced disk).
# ---------------------------------------------------------------------------

@dataclass
class HealingTracker:
    """Persisted on the drive being healed; survives restarts mid-heal
    (cf. healingTracker, /root/reference/cmd/background-newdisks-heal-ops.go:48)."""
    heal_id: str = ""
    started_ns: int = 0
    resume_bucket: str = ""
    resume_object: str = ""
    objects_healed: int = 0
    objects_failed: int = 0
    bytes_healed: int = 0
    finished: bool = False

    def save(self, drive: LocalDrive) -> None:
        drive.write_all(SYS_VOL, HEALING_FILE, msgpackx.packb({
            "id": self.heal_id, "start": self.started_ns,
            "rb": self.resume_bucket, "ro": self.resume_object,
            "oh": self.objects_healed, "of": self.objects_failed,
            "bh": self.bytes_healed, "fin": self.finished}))

    @classmethod
    def load(cls, drive: LocalDrive) -> "HealingTracker | None":
        try:
            d = msgpackx.unpackb(drive.read_all(SYS_VOL, HEALING_FILE))
        except StorageError:
            return None
        return cls(heal_id=d.get("id", ""), started_ns=d.get("start", 0),
                   resume_bucket=d.get("rb", ""),
                   resume_object=d.get("ro", ""),
                   objects_healed=d.get("oh", 0),
                   objects_failed=d.get("of", 0),
                   bytes_healed=d.get("bh", 0),
                   finished=d.get("fin", False))

    @staticmethod
    def clear(drive: LocalDrive) -> None:
        try:
            drive.delete(SYS_VOL, HEALING_FILE)
        except StorageError:
            pass


def _set_objects(es: ErasureSet, bucket: str, skip_pos: int) -> list[str]:
    """Union of object names for a bucket across all drives but skip_pos."""
    names: set[str] = set()
    for pos, d in enumerate(es.drives):
        if d is None or pos == skip_pos:
            continue
        try:
            for name, _ in d.walk_dir(bucket):
                names.add(name)
        except StorageError:
            continue
    return sorted(names)


def _heal_workers(es: ErasureSet, workers: int | None) -> int:
    """Bounded default: a couple of concurrent object heals per spare
    core, 1 on the serial-local host (same policy as the data-path
    fan-out, ErasureSet._SERIAL_FANOUT).  Under foreground pressure
    the overload plane shrinks the pool further — heal yields to
    GET/PUT for drives and coalescer lanes (server/qos.py)."""
    from ..server import qos as _qos
    if workers is not None:
        return _qos.scale_workers(max(1, int(workers)), "heal")
    n = 1 if es._serial_local() else min(4, os.cpu_count() or 1)
    return _qos.scale_workers(n, "heal")


def heal_drive(es: ErasureSet, pos: int, checkpoint_every: int = 64,
               workers: int | None = None,
               stop: threading.Event | None = None) -> HealingTracker:
    """Walk the whole set onto one (new/replaced/wiped) drive, resumably,
    healing up to `workers` objects concurrently through the reconstruct
    pipeline (bounded submission window — no unbounded queue growth).

    The HealingTracker checkpoint only ever advances over the CONTIGUOUS
    completed prefix of the sorted walk: with concurrent workers, object
    i+1 may finish before object i, and persisting i+1 as the resume
    point would skip i forever if the heal is interrupted mid-batch.
    Re-healing a beyond-frontier object on resume is a no-op.

    cf. healErasureSet, /root/reference/cmd/global-heal.go:166."""
    drive = es.drives[pos]
    if drive is None:
        raise ErrVolumeNotFound(f"drive position {pos} offline")
    tracker = HealingTracker.load(drive)
    if tracker is None or tracker.finished:
        tracker = HealingTracker(heal_id=str(uuid.uuid4()),
                                 started_ns=time.time_ns())
        tracker.save(drive)
    workers = _heal_workers(es, workers)

    def walk():
        for bucket in es.list_buckets():
            if bucket < tracker.resume_bucket:
                continue
            heal_bucket(es, bucket)
            for obj in _set_objects(es, bucket, skip_pos=pos):
                if (bucket == tracker.resume_bucket
                        and obj <= tracker.resume_object):
                    continue
                yield bucket, obj

    def heal_one(item):
        bucket, obj = item
        healed = nbytes = 0
        for r in heal_object(es, bucket, obj):
            if pos in r.healed_drives:
                healed += 1
                nbytes += r.size
        return healed, nbytes

    mu = threading.Lock()
    frontier = pl.Frontier()
    items: dict[int, tuple[str, str]] = {}
    done_below = 0          # items consumed by the frontier so far
    since_ckpt = 0
    pool = ThreadPoolExecutor(max_workers=workers) if workers > 1 else None
    try:
        for idx, item, res, err in pl.run_window(
                heal_one, walk(), pool, window=workers * 2, stop=stop):
            if err is not None and not isinstance(err, StorageError):
                raise err
            with mu:
                if err is not None:
                    tracker.objects_failed += 1
                else:
                    tracker.objects_healed += res[0]
                    tracker.bytes_healed += res[1]
                items[idx] = item
                front = frontier.mark(idx)
                while done_below < front:
                    tracker.resume_bucket, tracker.resume_object = \
                        items.pop(done_below)
                    done_below += 1
                    since_ckpt += 1
                if since_ckpt >= checkpoint_every:
                    tracker.save(drive)
                    since_ckpt = 0
    finally:
        if pool is not None:
            pool.shutdown(wait=True)
    if stop is None or not stop.is_set():
        tracker.finished = True
    tracker.save(drive)
    return tracker


def heal_bucket_objects(es: ErasureSet, bucket: str, prefix: str = "",
                        deep: bool = False, remove_dangling: bool = True,
                        workers: int | None = None,
                        stop: threading.Event | None = None,
                        on_object=None) -> list[HealResult]:
    """Heal every object in a bucket through the same bounded worker
    pool as heal_drive (the per-bucket arm of the background heal
    sequence). `on_object(name, results, err)` observes each object as
    it completes; non-storage errors propagate."""
    workers = _heal_workers(es, workers)
    names = [n for n in _set_objects(es, bucket, skip_pos=-1)
             if n.startswith(prefix)]

    def one(name):
        return heal_object(es, bucket, name, deep=deep,
                           remove_dangling=remove_dangling)

    results: list[HealResult] = []
    pool = ThreadPoolExecutor(max_workers=workers) if workers > 1 else None
    try:
        from ..server import qos as _qos
        for _, name, res, err in pl.run_window(
                one, names, pool, window=workers * 2, stop=stop):
            if err is not None and not isinstance(err, StorageError):
                raise err
            if on_object is not None:
                on_object(name, res, err)
            if err is None and res:
                results.extend(res)
            # Pace between objects under foreground pressure (no-op
            # below the threshold: one float compare per object).
            _qos.bg_pause("heal")
    finally:
        if pool is not None:
            pool.shutdown(wait=True)
    return results


def device_parallel_enabled() -> bool:
    """MTPU_HEAL_DEVICE_PARALLEL=0 is the serial-sweep oracle the
    equivalence tests diff against (read per call)."""
    return os.environ.get("MTPU_HEAL_DEVICE_PARALLEL", "1") != "0"


def sweep_sets_device_parallel(sets, job, stop: threading.Event | None = None):
    """Run `job(es)` over every erasure set with device-parallelism
    (PR 10): sets are grouped by their lane affinity (`es.device_idx`)
    and one worker thread per device runs its group's sets in order —
    per-set heal jobs against DIFFERENT devices dispatch concurrently
    while one device's own jobs stay serial (no oversubscribing a
    single accelerator queue, and within-device ordering matches the
    serial sweep).  With one group, a stop request, or the serial
    oracle flag, this degrades to the plain in-order loop.

    Returns {set_index: job result}.  The first exception any group
    raised is re-raised after every group finished — same containment
    the serial loop gets from its caller, but no set is silently
    skipped because a sibling on another device failed."""
    groups: dict[int, list] = {}
    for es in sets:
        groups.setdefault(getattr(es, "device_idx", 0), []).append(es)
    results: dict[int, object] = {}
    if not device_parallel_enabled() or len(groups) <= 1:
        for es in sets:
            if stop is not None and stop.is_set():
                break
            results[es.set_index] = job(es)
        return results
    mu = threading.Lock()
    errors: list[BaseException] = []

    def run_group(group):
        for es in group:
            if stop is not None and stop.is_set():
                return
            try:
                r = job(es)
            except BaseException as e:  # noqa: BLE001 — collect, re-raise
                with mu:
                    errors.append(e)
                return
            with mu:
                results[es.set_index] = r

    threads = [threading.Thread(target=run_group, args=(g,),
                                name=f"mtpu-heal-d{d}", daemon=True)
               for d, g in sorted(groups.items())]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return results
