"""erasureServerPools equivalent: the top-level ObjectLayer.

Pools are independent ErasureSets stacks added over time for capacity.
Writes go to the pool already holding the object, else the pool with the
most free space; reads/deletes probe pools in order (cf.
erasureServerPools.getPoolIdx, /root/reference/cmd/erasure-server-pool.go:373,
PutObject :812, GetObjectNInfo :661).
"""

from __future__ import annotations

from ..storage.errors import (ErrBucketExists, ErrBucketNotFound,
                              ErrObjectNotFound, ErrVersionNotFound,
                              StorageError)
from ..storage.xlmeta import FileInfo
from .sets import ErasureSets


class ServerPools:
    """The ObjectLayer facade over one or more pools."""

    def __init__(self, pools: list[ErasureSets]):
        if not pools:
            raise ValueError("need at least one pool")
        self.pools = pools
        self.deployment_id = pools[0].deployment_id
        # Pool indices excluded from NEW placement (decommission drain):
        # reads/deletes keep probing them, writes route elsewhere.
        self.draining: set[int] = set()
        # pool idx -> background.decom.Decommissioner (admin status).
        self.decommissions: dict[int, object] = {}
        # Pool-sticky multipart ids relocated off a drained pool:
        # old full upload id -> new full upload id (see background/decom).
        self.upload_relocations: dict[str, str] = {}

    # -- pool placement ------------------------------------------------------

    def set_draining(self, idx: int, flag: bool = True) -> None:
        if not 0 <= idx < len(self.pools):
            raise ValueError(f"no pool {idx}")
        if flag:
            if len(self.placement_pools()) <= 1 \
                    and idx not in self.draining:
                raise ValueError(
                    "cannot drain the last placement-eligible pool")
            self.draining.add(idx)
        else:
            self.draining.discard(idx)

    def placement_pools(self) -> list[int]:
        """Pool indices new writes may land on (draining excluded)."""
        out = [i for i in range(len(self.pools)) if i not in self.draining]
        return out or list(range(len(self.pools)))

    def _pool_with_object(self, bucket: str, obj: str,
                          version_id: str = "") -> int | None:
        for i, p in enumerate(self.pools):
            try:
                p.head_object(bucket, obj, version_id)
                return i
            except (ErrObjectNotFound, ErrVersionNotFound,
                    ErrBucketNotFound):
                continue
            # Anything else (e.g. read-quorum loss) must propagate: treating
            # a degraded pool as "object not here" would place an overwrite
            # PUT on another pool and leave a permanently stale duplicate.
        return None

    def get_pool_idx(self, bucket: str, obj: str) -> int:
        """Existing pool wins; else most free space, ties broken by the
        LOWEST pool index (cf. getPoolIdx, erasure-server-pool.go:373 —
        the deterministic tie-break keeps placement stable across
        restarts: equal-capacity pools must not flip-flop an object
        between pools on re-PUT).

        A sole candidate short-circuits BEFORE the existence probe (the
        reference's SinglePool() fast path): the probe needs read
        quorum, and a key whose last write died mid-publish (one drive
        holds the version — below quorum) would otherwise 503 every
        overwrite PUT forever.  With one eligible pool there is no
        placement decision to protect, so the write must always
        proceed.  Draining pools are excluded outright: an existing
        copy there must NOT attract the write (the decommission mover
        owns that copy), so the overwrite re-places by free space."""
        cands = self.placement_pools()
        if len(cands) == 1:
            return cands[0]
        existing = self._pool_with_object(bucket, obj)
        if existing is not None and existing not in self.draining:
            return existing
        frees = {i: self.pools[i].disk_usage()["free"] for i in cands}
        best = max(frees.values())
        return min(i for i in cands if frees[i] == best)

    # -- pool lifecycle ------------------------------------------------------

    def add_pool(self, es: ErasureSets) -> int:
        """Attach a freshly-formatted pool to a RUNNING deployment
        (cf. the reference's restart-time capacity expansion — here it
        is live, via the admin pool/add API).  The bucket set is
        replicated onto the new pool BEFORE it becomes placement-
        eligible, so a write routed there the instant it appears can
        never hit ErrBucketNotFound."""
        if es.deployment_id != self.deployment_id:
            raise ValueError(
                f"pool deployment id {es.deployment_id} != "
                f"{self.deployment_id}")
        for b in self.list_buckets():
            try:
                es.make_bucket(b)
            except ErrBucketExists:
                pass
        self.pools.append(es)
        # A pool adopted at runtime joins the shared hot tier the
        # original pools attached at boot (all-local sets only).
        tier = getattr(self, "hot_tier", None)
        if tier is not None:
            from .hotcache import attach_sets
            attach_sets(es, tier)
        self.build_ladders(pools=[es])
        return len(self.pools) - 1

    def build_ladders(self, parity: int | None = None,
                      pools: list[ErasureSets] | None = None) -> None:
        """Ask every set (of `pools`, default all) for the shape ladder
        of its device programs at `parity` (None: each set's default);
        see ErasureSet.build_ladder."""
        for p in self.pools if pools is None else pools:
            for es in p.sets:
                es.build_ladder(parity)

    # -- bucket ops ----------------------------------------------------------

    def make_bucket(self, bucket: str) -> None:
        """Fan out to ALL pools atomically: a hard failure on any pool
        rolls back the copies THIS call created (pre-existing copies
        stay), so the bucket never half-exists across pools."""
        created: list[int] = []
        errs = []
        for i, p in enumerate(self.pools):
            try:
                p.make_bucket(bucket)
                created.append(i)
                errs.append(None)
            except ErrBucketExists as e:
                errs.append(e)
            except StorageError:
                for j in created:
                    try:
                        self.pools[j].delete_bucket(bucket)
                    except StorageError:
                        pass        # best-effort unwind; state converges
                raise
        if errs and all(isinstance(e, ErrBucketExists) for e in errs):
            raise ErrBucketExists(bucket)

    def bucket_exists(self, bucket: str, cached: bool = False) -> bool:
        # cached=True is the write hot path's pre-check (see
        # ErasureSet.bucket_exists); explicit queries always stat.
        return any(p.bucket_exists(bucket, cached=cached)
                   for p in self.pools)

    def delete_bucket(self, bucket: str, force: bool = False) -> None:
        """Fan out to ALL pools atomically: a hard failure partway (the
        classic case — force=False and one pool still holds objects)
        re-creates the bucket on the pools already deleted from, so
        existence state converges instead of diverging (the old code
        deleted the empty pools' copies and then raised, leaving the
        bucket visible on some pools and gone on others)."""
        deleted: list[int] = []
        errs = []
        for i, p in enumerate(self.pools):
            try:
                p.delete_bucket(bucket, force=force)
                deleted.append(i)
                errs.append(None)
            except ErrBucketNotFound as e:
                errs.append(e)
            except StorageError:
                for j in deleted:
                    try:
                        self.pools[j].make_bucket(bucket)
                    except StorageError:
                        pass        # best-effort unwind; state converges
                raise
        if errs and all(isinstance(e, ErrBucketNotFound) for e in errs):
            raise ErrBucketNotFound(bucket)

    def list_buckets(self) -> list[str]:
        names: set[str] = set()
        for p in self.pools:
            names.update(p.list_buckets())
        return sorted(names)

    # -- object ops ----------------------------------------------------------

    def put_object(self, bucket: str, obj: str, data: bytes,
                   **kw) -> FileInfo:
        if not self.bucket_exists(bucket, cached=True):
            raise ErrBucketNotFound(bucket)
        idx = self.get_pool_idx(bucket, obj)
        fi = self.pools[idx].put_object(bucket, obj, data, **kw)
        try:
            # Placement tag for observability (the x-mtpu-pool response
            # header + loadgen's placement-skew histogram); never stored.
            fi.pool_idx = idx
        except (AttributeError, TypeError):
            pass
        return fi

    def _read_pool_idx(self, bucket: str, obj: str,
                       version_id: str = "") -> int | None:
        """Pool a read should serve from.  Normally first-hit probe
        order (placement guarantees at most one copy); while a drain is
        active the mover's copy-then-delete window can briefly hold the
        SAME object on two pools — and an overwrite during the drain
        lands on a non-draining pool while the stale source still
        shadows it in probe order — so reads become latest-wins
        (compare mod_time_ns across every pool that answers).  Named
        versions stay first-hit: version ids are unique."""
        if not self.draining or version_id:
            return self._pool_with_object(bucket, obj, version_id)
        best: tuple[int, int] | None = None    # (mod_time_ns, idx)
        for i, p in enumerate(self.pools):
            try:
                fi = p.head_object(bucket, obj, version_id)
            except (ErrObjectNotFound, ErrVersionNotFound,
                    ErrBucketNotFound):
                continue
            if best is None or fi.mod_time_ns > best[0]:
                best = (fi.mod_time_ns, i)
        return None if best is None else best[1]

    def get_object(self, bucket: str, obj: str, offset: int = 0,
                   length: int = -1, version_id: str = ""):
        last: StorageError | None = None
        if self.draining and not version_id:
            idx = self._read_pool_idx(bucket, obj)
            if idx is not None:
                return self.pools[idx].get_object(bucket, obj, offset,
                                                  length, version_id)
        else:
            for p in self.pools:
                try:
                    return p.get_object(bucket, obj, offset, length,
                                        version_id)
                except (ErrObjectNotFound, ErrVersionNotFound) as e:
                    last = e
        if not self.bucket_exists(bucket):
            raise ErrBucketNotFound(bucket)
        raise last or ErrObjectNotFound(f"{bucket}/{obj}")

    def get_object_iter(self, bucket: str, obj: str, offset: int = 0,
                        length: int = -1, version_id: str = ""):
        """Streaming read: (fi, chunk iterator); falls back to a whole-
        object read on backends without a streaming path."""
        last: StorageError | None = None
        order = list(self.pools)
        if self.draining and not version_id:
            idx = self._read_pool_idx(bucket, obj)
            order = [self.pools[idx]] if idx is not None else []
        for p in order:
            try:
                if hasattr(p, "get_object_iter"):
                    return p.get_object_iter(bucket, obj, offset, length,
                                             version_id)
                fi, data = p.get_object(bucket, obj, offset, length,
                                        version_id)
                return fi, iter((data,))
            except (ErrObjectNotFound, ErrVersionNotFound) as e:
                last = e
        if not self.bucket_exists(bucket):
            raise ErrBucketNotFound(bucket)
        raise last or ErrObjectNotFound(f"{bucket}/{obj}")

    def sendfile_plan(self, bucket: str, obj: str, offset: int = 0,
                      length: int = -1, version_id: str = ""):
        """Kernel-send plan (fi, [FilePlan...]) from the pool that owns
        the object, or None — never raises; the normal read path is the
        error oracle."""
        order = list(self.pools)
        if self.draining and not version_id:
            idx = self._read_pool_idx(bucket, obj)
            order = [self.pools[idx]] if idx is not None else []
        for p in order:
            sp = getattr(p, "sendfile_plan", None)
            if sp is None:
                continue
            try:
                got = sp(bucket, obj, offset, length, version_id)
            except StorageError:
                return None
            if got is not None:
                return got
        return None

    def head_object(self, bucket: str, obj: str,
                    version_id: str = "") -> FileInfo:
        last: StorageError | None = None
        if self.draining and not version_id:
            idx = self._read_pool_idx(bucket, obj)
            if idx is not None:
                return self.pools[idx].head_object(bucket, obj,
                                                   version_id)
        else:
            for p in self.pools:
                try:
                    return p.head_object(bucket, obj, version_id)
                except (ErrObjectNotFound, ErrVersionNotFound) as e:
                    last = e
        if not self.bucket_exists(bucket):
            raise ErrBucketNotFound(bucket)
        raise last or ErrObjectNotFound(f"{bucket}/{obj}")

    def delete_object(self, bucket: str, obj: str, version_id: str = "",
                      versioned: bool = False):
        if self.draining and not (versioned and version_id == ""):
            # Mid-drain an object can transiently live on two pools
            # (copied, source not yet reaped).  A hard delete must
            # remove EVERY copy — deleting only the first probe hit
            # would let the surviving duplicate resurrect the object.
            hit = False
            res = None
            for p in self.pools:
                try:
                    res = p.delete_object(bucket, obj, version_id,
                                          versioned)
                    hit = True
                except (ErrObjectNotFound, ErrVersionNotFound,
                        ErrBucketNotFound):
                    continue
            if hit:
                return res
            if not self.bucket_exists(bucket):
                raise ErrBucketNotFound(bucket)
            raise ErrObjectNotFound(f"{bucket}/{obj}")
        idx = self._pool_with_object(bucket, obj, version_id)
        if idx is None:
            if not self.bucket_exists(bucket):
                raise ErrBucketNotFound(bucket)
            if versioned and version_id == "":
                # Delete marker still lands on the placement pool.
                return self.pools[self.get_pool_idx(
                    bucket, obj)].delete_object(bucket, obj, version_id,
                                                versioned)
            raise ErrObjectNotFound(f"{bucket}/{obj}")
        return self.pools[idx].delete_object(bucket, obj, version_id,
                                             versioned)

    def list_objects(self, bucket: str, prefix: str = "",
                     marker: str = "",
                     max_keys: int = 10000) -> list[FileInfo]:
        if not self.bucket_exists(bucket):
            raise ErrBucketNotFound(bucket)
        merged: dict[str, FileInfo] = {}
        for p in self.pools:
            try:
                for fi in p.list_objects(bucket, prefix,
                                         marker=marker,
                                         max_keys=max_keys):
                    prev = merged.get(fi.name)
                    if prev is None or fi.mod_time_ns > prev.mod_time_ns:
                        merged[fi.name] = fi
            except ErrBucketNotFound:
                continue
        return [merged[k] for k in sorted(merged)][:max_keys]

    def list_object_names(self, bucket: str,
                          prefix: str = "") -> list[str]:
        names: set[str] = set()
        for p in self.pools:
            for es in getattr(p, "sets", [p]):
                try:
                    names.update(es.list_object_names(bucket, prefix))
                except StorageError:
                    continue
        return sorted(names)

    def list_object_versions(self, bucket: str, obj: str) -> list[FileInfo]:
        """Version history merged across pools (an overwrite during a
        drain legitimately splits an object's versions between the
        draining source and the destination), deduped by version id,
        newest first — the single-pool result is unchanged."""
        merged: dict[str, FileInfo] = {}
        found = False
        for p in self.pools:
            try:
                vers = p.list_object_versions(bucket, obj)
            except (ErrObjectNotFound, StorageError):
                continue
            found = True
            for fi in vers:
                prev = merged.get(fi.version_id)
                if prev is None or fi.mod_time_ns > prev.mod_time_ns:
                    merged[fi.version_id] = fi
        if not found:
            raise ErrObjectNotFound(f"{bucket}/{obj}")
        out = sorted(merged.values(),
                     key=lambda fi: (-fi.mod_time_ns, fi.version_id))
        for i, fi in enumerate(out):
            fi.is_latest = i == 0
        return out

    # -- multipart -----------------------------------------------------------

    def new_multipart_upload(self, bucket: str, obj: str, **kw) -> str:
        if not self.bucket_exists(bucket):
            raise ErrBucketNotFound(bucket)
        idx = self.get_pool_idx(bucket, obj)
        uid = self.pools[idx].new_multipart_upload(bucket, obj, **kw)
        # Uploads are pool-sticky: encode the pool into the id.
        return f"{idx}.{uid}"

    def _split_upload_id(self, upload_id: str) -> tuple[int, str]:
        # A drained pool's pending uploads were re-staged elsewhere; the
        # client still holds the OLD id, so follow the relocation map
        # (persisted in the decom journal, reloaded at boot).
        upload_id = self.upload_relocations.get(upload_id, upload_id)
        idx, _, rest = upload_id.partition(".")
        try:
            idx = int(idx)
        except ValueError:
            from .multipart import ErrUploadNotFound
            raise ErrUploadNotFound(upload_id) from None
        if not 0 <= idx < len(self.pools):
            from .multipart import ErrUploadNotFound
            raise ErrUploadNotFound(upload_id) from None
        return idx, rest

    def put_object_part(self, bucket: str, obj: str, upload_id: str,
                        part_number: int, data: bytes):
        idx, uid = self._split_upload_id(upload_id)
        return self.pools[idx].put_object_part(bucket, obj, uid,
                                               part_number, data)

    def complete_multipart_upload(self, bucket: str, obj: str,
                                  upload_id: str, parts, **kw):
        idx, uid = self._split_upload_id(upload_id)
        return self.pools[idx].complete_multipart_upload(bucket, obj, uid,
                                                         parts, **kw)

    def abort_multipart_upload(self, bucket: str, obj: str,
                               upload_id: str) -> None:
        idx, uid = self._split_upload_id(upload_id)
        self.pools[idx].abort_multipart_upload(bucket, obj, uid)

    def list_parts(self, bucket: str, obj: str, upload_id: str):
        idx, uid = self._split_upload_id(upload_id)
        return self.pools[idx].list_parts(bucket, obj, uid)

    def list_multipart_uploads(self, bucket: str,
                               prefix: str = "") -> list[dict]:
        out = []
        for i, p in enumerate(self.pools):
            for u in p.list_multipart_uploads(bucket, prefix):
                u = dict(u)
                u["upload_id"] = f"{i}.{u['upload_id']}"
                out.append(u)
        return sorted(out, key=lambda u: (u["object"], u["upload_id"]))

    def update_object_metadata(self, bucket: str, obj: str, fi) -> None:
        """Merge-updated FileInfo back onto the stripe (the
        updateObjectMetadata seam, cmd/erasure-object.go:1513).
        Erasure sets update per drive so each drive keeps its own
        inline shard + erasure index (ErasureSet.update_object_metadata);
        single-copy backends take the FileInfo whole."""
        for p in self.pools:
            for es in getattr(p, "sets", [p]):
                try:
                    if hasattr(es, "update_object_metadata"):
                        es.update_object_metadata(bucket, obj, fi)
                        return
                    res = es._map_drives(
                        lambda d: d.update_metadata(bucket, obj, fi))
                    if any(e is None for _, e in res):
                        return
                except StorageError:
                    continue
        raise ErrObjectNotFound(f"{bucket}/{obj}")

    # -- heal ----------------------------------------------------------------

    def heal_object(self, bucket: str, obj: str, version_id: str = "",
                    **kw):
        idx = self._pool_with_object(bucket, obj)
        if idx is None:
            raise ErrObjectNotFound(f"{bucket}/{obj}")
        return self.pools[idx].heal_object(bucket, obj, version_id, **kw)

    def heal_bucket(self, bucket: str) -> dict:
        out = {}
        for i, p in enumerate(self.pools):
            healed = p.heal_bucket(bucket)
            if healed:
                out[i] = healed
        return out

    # -- capacity / status ---------------------------------------------------

    def disk_usage(self) -> dict:
        """Cluster capacity summed over every pool (admin info / usage
        accounting see ONE namespace, not per-pool slices)."""
        total = free = 0
        for p in self.pools:
            du = p.disk_usage()
            total += du["total"]
            free += du["free"]
        return {"total": total, "free": free}

    def pool_status(self) -> list[dict]:
        """Per-pool capacity + drain state rows (admin `pools` listing
        and the mtpu_pool_* metric families)."""
        out = []
        for i, p in enumerate(self.pools):
            du = p.disk_usage()
            row = {"pool": i, "total": du["total"], "free": du["free"],
                   "draining": i in self.draining}
            d = self.decommissions.get(i)
            if d is not None:
                row["decommission"] = d.status()
            out.append(row)
        return out
