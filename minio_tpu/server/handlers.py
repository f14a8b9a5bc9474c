"""S3 API handlers: bucket/object/multipart surface over ServerPools.

The handler-layer equivalent of cmd/object-handlers.go /
cmd/bucket-handlers.go / cmd/bucket-listobjects-handlers.go, dispatched by
(method, path-shape, query) like cmd/api-router.go:175 registers routes.
Responses are S3 XML (cmd/api-response.go analogue in xml_responses.py).

Handlers speak to the ObjectLayer (engine.pools.ServerPools) only —
the same layering contract as the reference's layer 5 -> 6 boundary.
"""

from __future__ import annotations

import datetime
import email.utils
import hashlib
import time
import urllib.parse
import xml.etree.ElementTree as ET

from ..bucket.replication import ErrReplicationTargetDown
from ..engine.pools import ServerPools
from ..observe.span import span as _span
from ..storage.errors import ErrObjectNotFound, StorageError
from ..storage.xlmeta import FileInfo
from .api_errors import S3Error, from_storage_error

META_BUCKET = ".mtpu.sys"          # internal config bucket (minioMetaBucket)
MAX_OBJECT_SIZE = 5 * 1024 ** 4    # 5 TiB (docs/minio-limits.md)
MAX_KEY_LEN = 1024

# User metadata prefix passed through to storage.
AMZ_META_PREFIX = "x-amz-meta-"


def _iso(ns: int) -> str:
    dt = datetime.datetime.fromtimestamp(ns / 1e9, datetime.timezone.utc)
    return dt.strftime("%Y-%m-%dT%H:%M:%S.%f")[:-3] + "Z"


def _http_date(ns: int) -> str:
    return email.utils.formatdate(ns / 1e9, usegmt=True)


def _xml(root: ET.Element) -> bytes:
    return (b'<?xml version="1.0" encoding="UTF-8"?>'
            + ET.tostring(root, encoding="unicode").encode())


def _el(parent, tag, text=None):
    e = ET.SubElement(parent, tag)
    if text is not None:
        e.text = str(text)
    return e


S3_NS = "http://s3.amazonaws.com/doc/2006-03-01/"


class Response:
    def __init__(self, status: int = 200, body: bytes = b"",
                 headers: dict[str, str] | None = None,
                 body_iter=None, body_file=None):
        """body_iter: optional iterator of byte chunks streamed to the
        client instead of `body`; headers must carry Content-Length.
        body_file: optional list of ops.zerocopy.FilePlan — the body
        leaves via os.sendfile of verified shard runs (TLS/oracle
        writers materialize through plan.read_all()); headers must
        carry Content-Length."""
        self.status = status
        self.body = body
        self.body_iter = body_iter
        self.body_file = body_file
        self.headers = headers or {}


def error_response(err: S3Error, resource: str, request_id: str) -> Response:
    root = ET.Element("Error")
    _el(root, "Code", err.api.code)
    _el(root, "Message", err.message)
    _el(root, "Resource", resource)
    _el(root, "RequestId", request_id)
    return Response(err.api.http_status, _xml(root),
                    {"Content-Type": "application/xml"})


def _valid_bucket_name(name: str) -> bool:
    if not (3 <= len(name) <= 63) or name.startswith(".mtpu"):
        return False
    ok = set("abcdefghijklmnopqrstuvwxyz0123456789.-")
    return (all(c in ok for c in name) and not name.startswith((".", "-"))
            and not name.endswith((".", "-")))


class S3Handlers:
    """All bucket/object handlers; one instance per server."""

    def __init__(self, pools: ServerPools, *, notify=None,
                 replication=None, scanner=None, kms=None,
                 compress_enabled: bool = False, tier_mgr=None,
                 bucket_dns=None):
        from ..bucket.metadata import BucketMetadataSys
        from ..crypto.kms import kms_from_env
        self.pools = pools
        try:
            pools.make_bucket(META_BUCKET)
        except StorageError:
            pass
        self.meta = BucketMetadataSys(pools, META_BUCKET)
        self.notify = notify              # bucket.notify.NotificationSystem
        self.replication = replication    # bucket.replication.ReplicationPool
        self.scanner = scanner            # background.scanner.DataScanner
        # None when no master key configured: SSE-S3 PUTs are rejected
        # rather than sealed under a publicly-known key (ADVICE r2).
        self.kms = kms if kms is not None else kms_from_env()
        self.compress_enabled = compress_enabled
        self.tier_mgr = tier_mgr          # bucket.tier.TierManager
        self.bucket_dns = bucket_dns      # cluster.federation.BucketDNS
        # Built eagerly: a lazy property would race under the threaded
        # server and split the admin config plane (server.py shares
        # this instance) from the data path.
        from ..config.config import ConfigSys
        self.config_sys = ConfigSys(pools)

    # Client-visible size of a transformed (compressed/encrypted) object.
    CLIENT_SIZE_KEY = "x-mtpu-internal-client-size"

    # x-amz-storage-class -> storage_class config key (parity source,
    # cf. GetParityForSC at cmd/erasure-object.go:761 and
    # internal/config/storageclass/storage-class.go).
    SC_HEADER = "x-amz-storage-class"
    STORAGE_CLASSES = {"STANDARD": "standard", "REDUCED_REDUNDANCY": "rrs"}

    def _parity_for_request(self, h: dict, metadata: dict) -> int | None:
        """Parse x-amz-storage-class: validate, map through the
        storage_class config to a parity count, and record the class on
        the object (non-STANDARD only, like AWS listings)."""
        sc = h.get(self.SC_HEADER, "").upper()
        if not sc:
            return None
        if sc not in self.STORAGE_CLASSES:
            raise S3Error("InvalidStorageClass")
        if sc != "STANDARD":
            metadata[self.SC_HEADER] = sc
        return self.config_sys.parity_for_class(self.STORAGE_CLASSES[sc])

    def _logical_size(self, fi) -> int:
        from ..bucket.tier import TIER_SIZE_KEY
        if TIER_SIZE_KEY in fi.metadata:
            # transitioned stub: size of the tiered stored bytes; the
            # client-size key still wins if transforms applied
            return int(fi.metadata.get(self.CLIENT_SIZE_KEY,
                                       fi.metadata[TIER_SIZE_KEY]))
        return int(fi.metadata.get(self.CLIENT_SIZE_KEY, fi.size))

    def _is_transitioned(self, fi) -> bool:
        return (self.tier_mgr is not None
                and self.tier_mgr.is_transitioned(fi))

    def _proxy_get_response(self, bucket: str, key: str,
                            version_id: str, headers: dict,
                            head: bool):
        """Serve a GET whose local copy is missing from the bucket's
        replication target, reversing the stored transforms the
        replica's metadata records (proxyGetToReplicationTarget,
        cmd/bucket-replication.go:825) — or None to fall through to
        the 404. Version-pinned reads stay local: the target's
        version ids differ."""
        from ..crypto import sse
        from ..utils import compress as cz
        if self.replication is None or version_id:
            return None
        # Only while THIS bucket is actively resyncing: outside a
        # resync, a local miss means the object does not exist (or was
        # deleted) — proxying then would serve deleted objects from a
        # stale replica forever (the reference gates the proxy on the
        # resync window the same way).
        st = self.replication.resync_status(bucket)
        if not st or st.get("status") != "running":
            return None
        try:
            meta, data = self.replication.proxy_get(bucket, key)
        except ErrReplicationTargetDown as e:
            # The target might hold this key but cannot be reached — a
            # 404 here would lie to the client ("does not exist") when
            # the truth is "cannot know right now": surface 503.
            raise S3Error("ReplicationRemoteConnectionError",
                          str(e)) from None
        except StorageError:
            return None
        if sse.is_encrypted(meta):
            try:
                data = sse.decrypt_for_get(data, meta, headers,
                                           self.kms, bucket, key)
            except sse.SSEError as e:
                raise S3Error("AccessDenied", str(e)) from None
        data = cz.decompress(data, meta)
        # Conditional semantics survive the proxy: the replica carries
        # the source etag in its metadata.
        cond_fi = FileInfo(volume=bucket, name=key, size=len(data),
                           metadata=dict(meta))
        cond = self._check_conditions(headers, cond_fi)
        if cond is not None:
            return cond
        h = {"Content-Length": str(len(data)),
             "Content-Type": meta.get("content-type",
                                      "application/octet-stream"),
             "x-amz-replication-status": "REPLICA"}
        if meta.get("etag"):
            h["ETag"] = f'"{meta["etag"]}"'
        rng = headers.get("Range") or headers.get("range")
        if rng:
            parsed = self._parse_range(rng, len(data))
            if parsed:
                off, ln = parsed
                h["Content-Range"] = (
                    f"bytes {off}-{off + ln - 1}/{len(data)}")
                h["Content-Length"] = str(ln)
                # memoryview: the socket writer takes any buffer — no
                # copy of the ranged window.
                return Response(
                    206, b"" if head else memoryview(data)[off:off + ln], h)
        return Response(200, b"" if head else data, h)

    def _read_plaintext(self, bucket: str, key: str, version_id: str,
                        headers: dict) -> tuple:
        """Fetch an object and reverse its storage transforms
        (tier read-through -> decrypt -> decompress);
        returns (fi, plaintext)."""
        from ..crypto import sse
        from ..utils import compress as cz
        try:
            # One fetch; the stub body of a transitioned version is empty,
            # and checking the RETURNED fi (not a prior head) means a
            # concurrent transition can't hand us a stub we mistake for
            # data.
            fi, stored = self.pools.get_object(bucket, key,
                                               version_id=version_id)
            if self._is_transitioned(fi) \
                    and not self.tier_mgr.restore_fresh(fi):
                stored = self.tier_mgr.read_through(fi)
        except StorageError as e:
            raise from_storage_error(e) from None
        data = stored
        if sse.is_encrypted(fi.metadata):
            try:
                data = sse.decrypt_for_get(data, fi.metadata, headers,
                                           self.kms, bucket, key)
            except sse.SSEError as e:
                raise S3Error("AccessDenied", str(e)) from None
        data = cz.decompress(data, fi.metadata)
        return fi, data

    # ---- bucket config helpers (persisted via BucketMetadataSys) ----------

    def bucket_versioning_enabled(self, bucket: str) -> bool:
        data = self.meta.get(bucket, "versioning")
        return data is not None and b"<Status>Enabled</Status>" in data

    def _publish_event(self, event: str, bucket: str, key: str,
                       size: int = 0, etag: str = "",
                       version_id: str = "") -> None:
        if self.notify is not None:
            self.notify.publish(event, bucket, key, size=size, etag=etag,
                                version_id=version_id)

    def _lock_config(self, bucket: str) -> dict | None:
        from ..bucket import object_lock as ol
        data = self.meta.get(bucket, "object_lock")
        if data is None:
            return None
        try:
            return ol.parse_lock_config(data)
        except Exception:  # noqa: BLE001
            return None

    # ---- service level ----------------------------------------------------

    def list_buckets(self) -> Response:
        root = ET.Element("ListAllMyBucketsResult", xmlns=S3_NS)
        owner = _el(root, "Owner")
        _el(owner, "ID", "mtpu")
        _el(owner, "DisplayName", "mtpu")
        bl = _el(root, "Buckets")
        for b in self.pools.list_buckets():
            if b == META_BUCKET:
                continue
            be = _el(bl, "Bucket")
            _el(be, "Name", b)
            _el(be, "CreationDate", _iso(0))
        return Response(200, _xml(root), {"Content-Type": "application/xml"})

    # ---- bucket level -----------------------------------------------------

    def make_bucket(self, bucket: str) -> Response:
        if not _valid_bucket_name(bucket):
            raise S3Error("InvalidBucketName")
        if self.bucket_dns is not None:
            # Federation: bucket names are GLOBAL across the domain —
            # refuse names another cluster already published
            # (cf. the globalDNSConfig checks in cmd/bucket-handlers.go).
            try:
                if self.bucket_dns.owner_endpoint(bucket) is not None:
                    raise S3Error(
                        "BucketAlreadyExists",
                        "bucket owned by another federated cluster")
            except S3Error:
                raise
            except Exception as e:  # noqa: BLE001 — etcd down
                raise S3Error("ServiceUnavailable",
                              f"federation store unreachable: {e}") \
                    from None
        self.pools.make_bucket(bucket)
        if self.bucket_dns is not None:
            try:
                self.bucket_dns.put(bucket)
            except Exception as e:  # noqa: BLE001
                # Unpublished-but-existing would let another cluster
                # claim the same global name (split-brain) — roll the
                # local create back and fail loudly (the reference
                # deletes the bucket when the DNS publish fails,
                # cmd/bucket-handlers.go PutBucket).
                try:
                    self.pools.delete_bucket(bucket)
                except StorageError:
                    pass
                raise S3Error(
                    "ServiceUnavailable",
                    f"federation publish failed: {e}") from None
        return Response(200, headers={"Location": f"/{bucket}"})

    def head_bucket(self, bucket: str) -> Response:
        if not self.pools.bucket_exists(bucket) or bucket == META_BUCKET:
            raise S3Error("NoSuchBucket")
        return Response(200)

    def delete_bucket(self, bucket: str) -> Response:
        if self.pools.list_objects(bucket, max_keys=1):
            raise S3Error("BucketNotEmpty")
        self.pools.delete_bucket(bucket)
        self.meta.drop_bucket(bucket)
        if self.bucket_dns is not None:
            try:
                self.bucket_dns.delete(bucket)
            except Exception:  # noqa: BLE001
                pass
        return Response(204)

    def get_bucket_location(self, bucket: str) -> Response:
        self.head_bucket(bucket)
        root = ET.Element("LocationConstraint", xmlns=S3_NS)
        return Response(200, _xml(root), {"Content-Type": "application/xml"})

    def put_bucket_versioning(self, bucket: str, body: bytes) -> Response:
        self.head_bucket(bucket)
        self.meta.put(bucket, "versioning", body)
        return Response(200)

    def get_bucket_versioning(self, bucket: str) -> Response:
        self.head_bucket(bucket)
        data = self.meta.get(bucket, "versioning")
        root = ET.Element("VersioningConfiguration", xmlns=S3_NS)
        if data is not None and b"Enabled" in data:
            _el(root, "Status", "Enabled")
        return Response(200, _xml(root), {"Content-Type": "application/xml"})

    # ---- generic bucket sub-resource configs ------------------------------

    _CONFIG_KINDS = {
        "lifecycle": ("lifecycle", "NoSuchLifecycleConfiguration"),
        "policy": ("policy", "NoSuchBucketPolicy"),
        "notification": ("notification",
                         "NoSuchNotificationConfiguration"),
        "replication": ("replication",
                        "ReplicationConfigurationNotFoundError"),
        "quota": ("quota", "NoSuchBucketPolicy"),
        "object-lock": ("object_lock", "NoSuchObjectLockConfiguration"),
        "tagging": ("tagging", "NoSuchTagSet"),
        "encryption": ("encryption",
                       "ServerSideEncryptionConfigurationNotFoundError"),
    }

    def put_bucket_config(self, bucket: str, sub: str,
                          body: bytes) -> Response:
        self.head_bucket(bucket)
        kind, _ = self._CONFIG_KINDS[sub]
        wire_replication_after = False
        # Validate before storing (cf. per-config parse in
        # cmd/bucket-handlers.go).
        try:
            if kind == "lifecycle":
                from ..bucket.lifecycle import Lifecycle
                Lifecycle.parse(body)
            elif kind == "notification":
                from ..bucket.notify import parse_notification_config
                rules = parse_notification_config(body)
                if self.notify is not None:
                    self.notify.set_bucket_rules(bucket, rules)
            elif kind == "replication":
                from ..bucket.replication import (parse_replication_config,
                                                  parse_targets)
                rules = parse_replication_config(body)
                # Target wiring validates BEFORE the config persists:
                # a 400 here must not leave a half-persisted config
                # that re-fails its wiring at every boot. (Targets may
                # legitimately be absent entirely — wiring is then
                # deferred, matching wire_bucket's False return.)
                targets = parse_targets(
                    self.meta.get(bucket, "replication_targets"))
                if targets:
                    registered = {t.get("targetBucket", "")
                                  for t in targets}
                    unmatched = [r.target_bucket for r in rules
                                 if r.target_bucket not in registered]
                    if unmatched:
                        raise S3Error(
                            "InvalidArgument",
                            f"replication rules reference unregistered "
                            f"target bucket(s) {unmatched}; register "
                            f"them with admin bucket-remote first")
                # live wiring happens below once the config persists
                wire_replication_after = True
            elif kind == "object_lock":
                from ..bucket.object_lock import parse_lock_config
                parse_lock_config(body)
            elif kind == "quota":
                from ..bucket.quota import parse_quota_config
                cfg = parse_quota_config(body)
                if cfg["quota"] < 0 or cfg["bandwidth"] < 0:
                    raise S3Error(
                        "InvalidArgument",
                        "quota and bandwidth must be non-negative")
            elif kind == "policy":
                from ..iam.policy import Policy
                Policy(body.decode())
        except S3Error:
            raise
        except Exception:  # noqa: BLE001 — any parse failure
            raise S3Error("MalformedXML") from None
        self.meta.put(bucket, kind, body)
        if wire_replication_after and self.replication is not None:
            from ..bucket.replication import wire_bucket
            try:
                wire_bucket(self.replication, self.meta, bucket)
            except Exception as e:  # noqa: BLE001 — wire_bucket returns
                # False when targets are simply absent; an EXCEPTION
                # means corrupt registration data — a 200 with silently
                # dead replication would hide it from the operator.
                # Roll the just-persisted config back (fallback for
                # anything the pre-persist validation couldn't see,
                # e.g. a target unregistered in the races-with-us
                # window) so boot never replays a known-bad config.
                try:
                    self.meta.delete(bucket, kind)
                except Exception:  # noqa: BLE001 — rollback best-effort
                    pass
                raise S3Error("InvalidArgument",
                              f"replication wiring: {e}") from None
        return Response(200)

    def get_bucket_config(self, bucket: str, sub: str) -> Response:
        self.head_bucket(bucket)
        kind, missing_code = self._CONFIG_KINDS[sub]
        data = self.meta.get(bucket, kind)
        if data is None:
            raise S3Error(missing_code)
        ctype = ("application/json" if kind in ("policy", "quota")
                 else "application/xml")
        return Response(200, data, {"Content-Type": ctype})

    def delete_bucket_config(self, bucket: str, sub: str) -> Response:
        self.head_bucket(bucket)
        kind, _ = self._CONFIG_KINDS[sub]
        self.meta.delete(bucket, kind)
        if kind == "notification" and self.notify is not None:
            self.notify.set_bucket_rules(bucket, [])
        if kind == "replication" and self.replication is not None:
            # replication must stop NOW, not at next restart
            self.replication.unconfigure(bucket)
        return Response(204)

    # ---- listing ----------------------------------------------------------

    @staticmethod
    def _group_by_delimiter(infos: list[FileInfo], prefix: str,
                            delimiter: str):
        contents, prefixes, seen = [], [], set()
        for fi in infos:
            rest = fi.name[len(prefix):]
            if delimiter and delimiter in rest:
                cp = prefix + rest.split(delimiter)[0] + delimiter
                if cp not in seen:
                    seen.add(cp)
                    prefixes.append(cp)
            else:
                contents.append(fi)
        return contents, prefixes

    def list_objects(self, bucket: str, query: dict) -> Response:
        v2 = query.get("list-type", [""])[0] == "2"
        prefix = query.get("prefix", [""])[0]
        delimiter = query.get("delimiter", [""])[0]
        max_keys = min(int(query.get("max-keys", ["1000"])[0] or 1000), 1000)
        if v2:
            marker = query.get("continuation-token", [""])[0] or \
                query.get("start-after", [""])[0]
        else:
            marker = query.get("marker", [""])[0]
        self.head_bucket(bucket)

        infos = self.pools.list_objects(bucket, prefix, max_keys=100000)
        if marker:
            infos = [fi for fi in infos if fi.name > marker]
        contents, prefixes = self._group_by_delimiter(infos, prefix, delimiter)

        # Merge and truncate in lexical order over both kinds of entries.
        entries = sorted(
            [("o", fi.name, fi) for fi in contents]
            + [("p", p, None) for p in prefixes], key=lambda t: t[1])
        truncated = len(entries) > max_keys
        entries = entries[:max_keys]
        next_marker = entries[-1][1] if (truncated and entries) else ""

        root = ET.Element("ListBucketResult", xmlns=S3_NS)
        _el(root, "Name", bucket)
        _el(root, "Prefix", prefix)
        if delimiter:
            _el(root, "Delimiter", delimiter)
        _el(root, "MaxKeys", max_keys)
        _el(root, "IsTruncated", "true" if truncated else "false")
        if v2:
            _el(root, "KeyCount", len(entries))
            if truncated:
                _el(root, "NextContinuationToken", next_marker)
        else:
            _el(root, "Marker", marker)
            if truncated:
                _el(root, "NextMarker", next_marker)
        for kind, name, fi in entries:
            if kind == "p":
                cp = _el(root, "CommonPrefixes")
                _el(cp, "Prefix", name)
            else:
                c = _el(root, "Contents")
                _el(c, "Key", name)
                _el(c, "LastModified", _iso(fi.mod_time_ns))
                _el(c, "ETag", f'"{fi.metadata.get("etag", "")}"')
                _el(c, "Size", self._logical_size(fi))
                _el(c, "StorageClass",
                    fi.metadata.get(self.SC_HEADER, "STANDARD"))
        return Response(200, _xml(root), {"Content-Type": "application/xml"})

    def list_object_versions(self, bucket: str, query: dict) -> Response:
        """GET /bucket?versions (cf. ListObjectVersionsHandler,
        cmd/bucket-listobjects-handlers.go)."""
        prefix = query.get("prefix", [""])[0]
        max_keys = min(int(query.get("max-keys", ["1000"])[0] or 1000),
                       1000)
        key_marker = query.get("key-marker", [""])[0]
        vid_marker = query.get("version-id-marker", [""])[0]
        self.head_bucket(bucket)
        root = ET.Element("ListVersionsResult", xmlns=S3_NS)
        _el(root, "Name", bucket)
        _el(root, "Prefix", prefix)
        _el(root, "MaxKeys", max_keys)
        if key_marker:
            _el(root, "KeyMarker", key_marker)
        if vid_marker:
            _el(root, "VersionIdMarker", vid_marker)
        truncated_el = _el(root, "IsTruncated", "false")
        count = 0
        lister = getattr(self.pools, "list_object_names", None)
        if lister is not None:
            names = lister(bucket, prefix)
        else:
            # FS/gateway fallback: list_objects caps; grow the window
            # until it covers the marker with a full page to spare, so
            # big buckets page correctly instead of silently truncating.
            cap = 100000
            while True:
                names = [fi.name for fi in
                         self.pools.list_objects(bucket, prefix,
                                                 max_keys=cap)]
                after = ([n for n in names if n > key_marker]
                         if key_marker else names)
                if len(names) < cap or len(after) > max_keys:
                    break
                cap *= 2
        names = sorted(n for n in names if n >= key_marker) \
            if key_marker else sorted(names)
        past_vid_marker = not vid_marker
        last_emitted = ("", "")
        for name in names:
            try:
                versions = self.pools.list_object_versions(bucket, name)
            except StorageError:
                continue
            if name == key_marker and vid_marker and not past_vid_marker:
                # Marker version deleted between pages: losing the rest
                # of the key's history is worse than re-emitting it —
                # treat a missing marker as "start of key".
                vids = {v.version_id or "null" for v in versions}
                if vid_marker not in vids:
                    past_vid_marker = True
            for v in versions:
                vid = v.version_id or "null"
                if name == key_marker:
                    # resume strictly after the marker version
                    if not past_vid_marker:
                        if vid == vid_marker:
                            past_vid_marker = True
                        continue
                    if not vid_marker:
                        continue        # key-marker alone: skip its key
                if count >= max_keys:
                    # markers name the LAST RETURNED item (AWS
                    # semantics); the next page resumes strictly after
                    truncated_el.text = "true"
                    _el(root, "NextKeyMarker", last_emitted[0])
                    _el(root, "NextVersionIdMarker", last_emitted[1])
                    return Response(200, _xml(root),
                                    {"Content-Type": "application/xml"})
                last_emitted = (name, vid)
                tag = "DeleteMarker" if v.deleted else "Version"
                e = _el(root, tag)
                _el(e, "Key", v.name or name)
                _el(e, "VersionId", vid)
                _el(e, "IsLatest", "true" if v.is_latest else "false")
                _el(e, "LastModified", _iso(v.mod_time_ns))
                if not v.deleted:
                    _el(e, "ETag", f'"{v.metadata.get("etag", "")}"')
                    _el(e, "Size", self._logical_size(v))
                count += 1
        return Response(200, _xml(root), {"Content-Type": "application/xml"})

    # ---- object level -----------------------------------------------------

    @staticmethod
    def _object_headers(fi: FileInfo) -> dict[str, str]:
        h = {
            "ETag": f'"{fi.metadata.get("etag", "")}"',
            "Last-Modified": _http_date(fi.mod_time_ns),
            "Content-Type": fi.metadata.get(
                "content-type", "application/octet-stream"),
            "Accept-Ranges": "bytes",
        }
        if fi.version_id:
            h["x-amz-version-id"] = fi.version_id
        if S3Handlers.SC_HEADER in fi.metadata:
            h[S3Handlers.SC_HEADER] = fi.metadata[S3Handlers.SC_HEADER]
        if "x-amz-replication-status" in fi.metadata:
            h["x-amz-replication-status"] = \
                fi.metadata["x-amz-replication-status"]
        from ..bucket.tier import RESTORE_EXPIRY_KEY, TIER_NAME_KEY
        if TIER_NAME_KEY in fi.metadata:
            # Transitioned stub: the tier name IS the storage class the
            # client sees; a live temporary restore adds x-amz-restore
            # (cf. postRestoreOpts, cmd/object-handlers.go).
            h[S3Handlers.SC_HEADER] = fi.metadata[TIER_NAME_KEY]
            exp = fi.metadata.get(RESTORE_EXPIRY_KEY)
            if exp:
                try:
                    h["x-amz-restore"] = (
                        'ongoing-request="false", expiry-date="'
                        + _http_date(int(float(exp) * 1e9)) + '"')
                except ValueError:
                    pass
        for k, v in fi.metadata.items():
            if k.startswith(AMZ_META_PREFIX):
                h[k] = v
        return h

    @staticmethod
    def _check_conditions(headers: dict[str, str],
                          fi: FileInfo) -> Response | None:
        """If-Match / If-None-Match / If-(Un)modified-Since with RFC
        7232 §6 precedence (cf. checkPreconditions,
        cmd/object-handlers-common.go): If-Match beats
        If-Unmodified-Since, If-None-Match beats If-Modified-Since.

        Returns a body-less 304 Response (carrying the §4.1-required
        ETag/Last-Modified validators, NOT an XML error body — clients
        revalidate their cache from these headers) when the client's
        copy is fresh, or None to proceed; a failed writer-side
        precondition raises S3Error("PreconditionFailed") → 412.

        Runs BEFORE any range parse or shard IO: the cheapest possible
        hot-key hit is the one that never touches a drive.
        """
        etag = fi.metadata.get("etag", "")
        h = {k.lower(): v for k, v in headers.items()}

        def etag_match(spec: str) -> bool:
            # Comma-separated entity-tag list; W/ weak tags compare by
            # opaque value (weak comparison is fine for GET/HEAD).
            if spec.strip() == "*":
                return True
            for cand in spec.split(","):
                cand = cand.strip()
                if cand.startswith("W/"):
                    cand = cand[2:]
                if cand.strip('"') == etag:
                    return True
            return False

        def parse_http_date(s):
            try:
                d = email.utils.parsedate_to_datetime(s)
            except (TypeError, ValueError):
                return None
            if d is not None and d.tzinfo is None:
                d = d.replace(tzinfo=datetime.timezone.utc)
            return d

        mod = datetime.datetime.fromtimestamp(
            fi.mod_time_ns / 1e9, datetime.timezone.utc).replace(microsecond=0)
        im = h.get("if-match")
        if im is not None:
            if not etag_match(im):
                raise S3Error("PreconditionFailed")
        else:
            ius = parse_http_date(h.get("if-unmodified-since", ""))
            if ius is not None and mod > ius:
                raise S3Error("PreconditionFailed")

        def not_modified() -> Response:
            nh = {"ETag": f'"{etag}"',
                  "Last-Modified": _http_date(fi.mod_time_ns)}
            if fi.version_id:
                nh["x-amz-version-id"] = fi.version_id
            return Response(304, b"", nh)

        inm = h.get("if-none-match")
        if inm is not None:
            if etag_match(inm):
                return not_modified()
        else:
            ims = parse_http_date(h.get("if-modified-since", ""))
            if ims is not None and mod <= ims:
                return not_modified()
        return None

    @staticmethod
    def _parse_range(spec: str, size: int) -> tuple[int, int] | None:
        """HTTP Range -> (offset, length). cf. cmd/httprange.go."""
        if not spec.startswith("bytes="):
            return None
        r = spec[len("bytes="):]
        if "," in r:
            raise S3Error("InvalidRange", "multiple ranges not supported")
        start_s, _, end_s = r.partition("-")
        try:
            if start_s == "":                   # suffix: last N bytes
                n = int(end_s)
                if n == 0:
                    raise S3Error("InvalidRange")
                start = max(size - n, 0)
                return start, size - start
            start = int(start_s)
            end = int(end_s) if end_s else size - 1
        except ValueError:
            # RFC 7233: a syntactically malformed Range is IGNORED
            # (whole object), not a 416.
            return None
        if start >= size:
            raise S3Error("InvalidRange")
        end = min(end, size - 1)
        if end < start:
            raise S3Error("InvalidRange")
        return start, end - start + 1

    def get_object(self, bucket: str, key: str, query: dict,
                   headers: dict[str, str], head: bool = False) -> Response:
        from ..crypto import sse
        from ..utils import compress as cz
        from . import extract as ex
        version_id = query.get("versionId", [""])[0]
        if ex.is_zip_extract_get(headers):
            split = ex.split_zip_path(key)
            if split is not None:
                zip_key, member = split
                _, zip_bytes = self._read_plaintext(bucket, zip_key,
                                                    version_id, headers)
                data = ex.read_zip_member(zip_bytes, member)
                h = {"Content-Length": str(len(data)),
                     "Content-Type": "application/octet-stream",
                     "Accept-Ranges": "none"}
                return Response(200, b"" if head else data, h)
        try:
            fi = self.pools.head_object(bucket, key, version_id)
        except ErrObjectNotFound as e:
            resp = self._proxy_get_response(bucket, key, version_id,
                                            headers, head)
            if resp is None:
                raise from_storage_error(e) from None
            return resp
        except StorageError as e:
            raise from_storage_error(e) from None
        cond = self._check_conditions(headers, fi)
        if cond is not None:
            return cond

        # A transitioned stub without other transforms streams straight
        # from its tier; with SSE/compression the whole-decode path
        # below applies.  A fresh temporary restore serves the hot body
        # like any other object.
        tiered = (self._is_transitioned(fi)
                  and not self.tier_mgr.restore_fresh(fi))
        transcoded = (sse.is_encrypted(fi.metadata)
                      or cz.is_compressed(fi.metadata))
        transformed = transcoded or tiered
        size = self._logical_size(fi)
        rng = headers.get("Range") or headers.get("range")
        offset, length = 0, size
        partial = False
        if rng:
            parsed = self._parse_range(rng, size)
            if parsed:
                offset, length = parsed
                partial = True
        data = b""
        body_iter = None
        body_file = None
        if not head:
            if tiered and not transcoded:
                # Restore-on-GET: stream the tier object in bounded
                # chunks, ranged offsets passed straight through — no
                # whole-object buffer (satellite: a 1 GiB cold GET is
                # O(chunk)).  The eager first pull surfaces tier-down
                # errors while they can still become S3 responses.
                import itertools
                try:
                    body_iter = self.tier_mgr.read_through_iter(
                        fi, offset, length)
                    first = next(body_iter, b"")
                except StorageError as e:
                    raise from_storage_error(e) from None
                body_iter = itertools.chain((first,), body_iter)
            elif transformed:
                # Ranged reads on transformed objects decode the whole
                # stream then slice by logical offsets (cf. the decrypt/
                # decompress cleanup stack in GetObjectReader,
                # cmd/object-api-utils.go:528).  The slice is a
                # memoryview: the decoded plaintext is already the only
                # full-size buffer, and the socket writer takes any
                # buffer — no second copy of the ranged window.
                fi, full = self._read_plaintext(bucket, key, version_id,
                                                headers)
                data = memoryview(full)[offset:offset + length]
            else:
                # Untransformed data streams straight off the erasure
                # engine in device-batch chunks — O(batch) memory
                # (the GetObjectReader role without a cleanup stack).
                try:
                    # Whole healthy GETs of kernel-sendable layouts get
                    # a verified sendfile plan: the body never enters
                    # the process (ops/zerocopy.py).  None on any gate
                    # miss — ranged, cached, inline, degraded, flag off.
                    sp = getattr(self.pools, "sendfile_plan", None)
                    if sp is not None:
                        with _span("engine.sendfile_plan"):
                            got = sp(bucket, key, offset, length,
                                     version_id)
                        if got is not None:
                            fi, body_file = got
                    if body_file is not None:
                        pass
                    elif hasattr(self.pools, "get_object_iter"):
                        with _span("engine.get_object"):
                            fi, body_iter = self.pools.get_object_iter(
                                bucket, key, offset, length, version_id)
                            # Pull the FIRST chunk eagerly: once
                            # headers are on the wire a failure can
                            # only sever the connection, so quorum/
                            # bitrot errors that surface immediately
                            # must still become S3 error responses.
                            import itertools
                            first = next(body_iter, b"")
                        body_iter = itertools.chain((first,), body_iter)
                    else:        # FS/gateway layers: whole-object read
                        with _span("engine.get_object"):
                            fi, data = self.pools.get_object(
                                bucket, key, offset, length, version_id)
                except StorageError as e:
                    raise from_storage_error(e) from None
        elif transformed and sse.is_encrypted(fi.metadata):
            # HEAD on SSE-C must still verify the presented key.
            algo = fi.metadata.get(sse.META_ALGO)
            if algo == "SSE-C":
                try:
                    k = sse.parse_ssec_key(headers)
                except sse.SSEError as e:
                    raise S3Error("AccessDenied", str(e)) from None
                import base64
                import hashlib as _hl
                if k is None or base64.b64encode(
                        _hl.md5(k).digest()).decode() != \
                        fi.metadata.get(sse.META_KEY_MD5, ""):
                    raise S3Error("AccessDenied",
                                  "SSE-C key required for HEAD")

        h = self._object_headers(fi)
        h.update(sse.response_headers(fi.metadata))
        if partial:
            h["Content-Range"] = \
                f"bytes {offset}-{offset + length - 1}/{size}"
            h["Content-Length"] = str(length)
            status = 206
        else:
            h["Content-Length"] = str(size)
            status = 200
        if head:
            return Response(status, b"", h)
        return Response(status, data, h, body_iter=body_iter,
                        body_file=body_file)

    def select_object_content(self, bucket: str, key: str, query: dict,
                              body: bytes,
                              headers: dict[str, str]) -> Response:
        """POST /bucket/key?select&select-type=2
        (cf. SelectObjectContentHandler, cmd/object-handlers.go:101)."""
        from ..s3select.engine import execute_select, parse_select_request
        from ..s3select.sql import SQLError
        import xml.etree.ElementTree as ETmod
        try:
            opts = parse_select_request(body)
        except ETmod.ParseError:
            raise S3Error("MalformedXML") from None
        version_id = query.get("versionId", [""])[0]
        _, data = self._read_plaintext(bucket, key, version_id, headers)
        try:
            out = execute_select(data, opts)
        except SQLError as e:
            raise S3Error("SelectParseError", str(e)) from None
        except Exception as e:  # noqa: BLE001 — bad data/query combos
            raise S3Error("SelectParseError",
                          f"{type(e).__name__}: {e}") from None
        return Response(200, out,
                        {"Content-Type": "application/octet-stream"})

    def put_object(self, bucket: str, key: str, body,
                   headers: dict[str, str]) -> Response:
        """`body` is bytes or a reader.  A reader streams straight into
        the erasure engine in O(batch) memory; transforms that need the
        whole object in memory (compression, SSE sealing, snowball
        extract, Content-MD5 verification) drain it first."""
        if len(key) > MAX_KEY_LEN:
            raise S3Error("KeyTooLongError")
        h = {k.lower(): v for k, v in headers.items()}
        from ..crypto import sse as _sse
        from ..utils import digestlanes, streams
        from . import extract as ex
        if "x-amz-copy-source" in h:
            if streams.is_reader(body):
                # Copy requests carry no meaningful body; drain so the
                # keep-alive socket isn't left desynced.
                while body.read(1 << 20):
                    pass
            return self._copy_object(bucket, key, h)
        # aws-chunked bodies declare the PAYLOAD length separately; the
        # wire Content-Length includes chunk headers + signatures.
        declared_size = (len(body) if isinstance(body, (bytes, bytearray))
                         else int(h.get("x-amz-decoded-content-length")
                                  or h.get("content-length") or 0))
        if declared_size > MAX_OBJECT_SIZE:
            raise S3Error("EntityTooLarge")
        if streams.is_reader(body):
            # Hard cap BEFORE any draining: an undeclared-length
            # (chunked TE) body must not grow past the object limit, in
            # memory or on disk.
            body = streams.MaxSizeReader(
                body, MAX_OBJECT_SIZE,
                exc=lambda msg: S3Error("EntityTooLarge"))
            if (ex.is_snowball_put(headers) or self.compress_enabled
                    or h.get("content-md5") or h.get(_sse.H_SSE)
                    or h.get(_sse.H_SSEC_ALGO)):
                body = streams.ensure_bytes(body)
                declared_size = len(body)
        if ex.is_snowball_put(headers):
            # Auto-extract a tar body into individual objects under the
            # key prefix (cf. PutObjectExtract, cmd/untar.go:100).
            n = 0
            for sub_key, data, _meta in ex.extract_tar(body, key):
                self.put_object(bucket, sub_key, data, {})
                n += 1
            return Response(200, headers={"x-mtpu-extracted-objects":
                                          str(n)})
        md5_hdr = h.get("content-md5")
        if md5_hdr:
            # Conformance split (cf. internal/hash/reader.go): a header
            # that does not decode to exactly one MD5 digest is
            # InvalidDigest; a well-formed digest that disagrees with
            # the body is BadDigest.  validate=True matters — lenient
            # b64decode silently drops non-alphabet bytes and would
            # misreport malformed headers as mismatches.  Runs before
            # put_object, so nothing is staged for a rejected body.
            import base64
            try:
                want = base64.b64decode(md5_hdr, validate=True)
            except Exception:  # noqa: BLE001
                raise S3Error("InvalidDigest") from None
            if len(want) != 16:
                raise S3Error("InvalidDigest")
            if digestlanes.md5_digest(body) != want:
                raise S3Error("BadDigest")
        metadata = {k: v for k, v in h.items()
                    if k.startswith(AMZ_META_PREFIX)}
        if "content-type" in h:
            metadata["content-type"] = h["content-type"]
        # incoming replica writes carry the replication status; storing
        # it makes GET/HEAD report REPLICA and suppresses re-replication
        # (active-active loop guard, cf. ReplicateObjectAction)
        is_replica = h.get("x-amz-replication-status") == "REPLICA"
        if is_replica:
            metadata["x-amz-replication-status"] = "REPLICA"
        # Version fidelity: a replica PUT lands under the SOURCE
        # version id + mod time so the two clusters' histories match
        # id-for-id and a replayed copy REPLACES instead of
        # duplicating. The server strips these headers from any
        # principal without s3:ReplicateObject, like the REPLICA
        # marker itself.
        replica_vid = h.get("x-mtpu-repl-version-id", "") \
            if is_replica else ""
        replica_mtime = 0
        if is_replica and h.get("x-mtpu-repl-mtime"):
            try:
                replica_mtime = int(h["x-mtpu-repl-mtime"])
            except ValueError:
                replica_mtime = 0
        parity = self._parity_for_request(h, metadata)

        # Quota enforcement (cf. enforceBucketQuotaHard,
        # cmd/bucket-quota.go).
        quota_raw = self.meta.get(bucket, "quota")
        if quota_raw is not None:
            from ..bucket import quota as bq
            qcfg = bq.parse_quota_config(quota_raw)
            reason = bq.check_quota(self.pools, bucket, declared_size,
                                    qcfg, self.scanner)
            if reason:
                raise S3Error("QuotaExceeded", reason)
            if streams.is_reader(body) and not declared_size \
                    and qcfg.get("quota", 0) > 0:
                # Undeclared-length stream on a quota'd bucket: cap at
                # the remaining allowance so chunked TE can't bypass it.
                remaining = max(0, qcfg["quota"]
                                - bq.current_bucket_bytes(
                                    self.pools, bucket, self.scanner))
                body = streams.MaxSizeReader(
                    body, remaining,
                    exc=lambda msg: S3Error("QuotaExceeded", msg))

        # Object-lock: existing protected version must not be silently
        # replaced (unversioned overwrite destroys it); default retention
        # from the bucket config applies to the new version. The same
        # pre-head also spots a transitioned stub an unversioned
        # overwrite is about to destroy — its tier object must be freed
        # or the cold copy leaks forever.
        lock_cfg = self._lock_config(bucket)
        versioned = self.bucket_versioning_enabled(bucket)
        prev = None
        if not versioned and (self.tier_mgr is not None
                              or (lock_cfg is not None
                                  and lock_cfg.get("enabled"))):
            try:
                prev = self.pools.head_object(bucket, key)
            except StorageError:
                prev = None
        if lock_cfg is not None and lock_cfg.get("enabled"):
            from ..bucket import object_lock as ol
            if prev is not None:
                reason = ol.check_delete_allowed(prev.metadata)
                if reason:
                    raise S3Error("ObjectLocked", reason)
            metadata.update(ol.default_retention_metadata(lock_cfg))
            # explicit per-request retention headers win
            for hk in (ol.RET_MODE_KEY, ol.RET_DATE_KEY, ol.LEGAL_HOLD_KEY):
                if hk in h:
                    metadata[hk] = h[hk]
        replaced_tiered = (prev is not None and self.tier_mgr is not None
                          and self.tier_mgr.is_transitioned(prev))

        # Storage transforms: compress, then encrypt (the reference
        # composes the same way — compressed plaintext is sealed,
        # cf. cmd/object-api-utils.go:903 + cmd/encryption-v1.go:303).
        from ..crypto import sse
        from ..utils import compress as cz
        stored = body
        transform_meta: dict = {}
        if self.compress_enabled and cz.is_compressible(
                key, metadata.get("content-type", ""), len(body)):
            stored, cu = cz.compress(stored)
            transform_meta.update(cu)
        try:
            stored, su = sse.encrypt_for_put(stored, h, self.kms,
                                             bucket, key)
        except sse.SSEError as e:
            raise S3Error("InvalidArgument", str(e)) from None
        transform_meta.update(su)
        if transform_meta:
            transform_meta[self.CLIENT_SIZE_KEY] = str(len(body))
            metadata.update(transform_meta)

        put_kw = {}
        if replica_vid and versioned:
            put_kw["version_id"] = replica_vid
        if replica_mtime:
            put_kw["mod_time_ns"] = replica_mtime
        try:
            with _span("engine.put_object"):
                fi = self.pools.put_object(bucket, key, stored,
                                           metadata=metadata,
                                           versioned=versioned,
                                           parity=parity, **put_kw)
        except StorageError as e:
            raise from_storage_error(e) from None
        if replaced_tiered:
            self.tier_mgr.on_version_deleted(prev)
        etag = fi.metadata.get("etag", "")
        self._publish_event("s3:ObjectCreated:Put", bucket, key,
                            size=self._logical_size(fi), etag=etag,
                            version_id=fi.version_id)
        if self.replication is not None and not is_replica:
            self.replication.on_put(bucket, key,
                                    version_id=fi.version_id or "")
        resp_headers = {"ETag": f'"{etag}"'}
        if fi.version_id:
            resp_headers["x-amz-version-id"] = fi.version_id
        pool_idx = getattr(fi, "pool_idx", None)
        if pool_idx is not None:
            # Placement tag (loadgen --during-decom reads this into the
            # per-pool skew histogram; harmless to normal clients).
            resp_headers["x-mtpu-pool"] = str(pool_idx)
        return Response(200, headers=resp_headers)

    def _copy_object(self, bucket: str, key: str,
                     h: dict[str, str]) -> Response:
        src = urllib.parse.unquote(h["x-amz-copy-source"]).lstrip("/")
        src_bucket, _, src_key = src.partition("/")
        src_vid = ""
        if "?versionId=" in src_key:
            src_key, _, src_vid = src_key.partition("?versionId=")
        try:
            fi, data = self.pools.get_object(src_bucket, src_key,
                                             version_id=src_vid)
        except StorageError as e:
            raise from_storage_error(e) from None
        metadata = dict(fi.metadata)
        metadata.pop("etag", None)
        if h.get("x-amz-metadata-directive", "COPY") == "REPLACE":
            # REPLACE swaps the USER metadata only; the internal
            # transform keys (compression marker, SSE envelope, client
            # size) describe the stored bytes being copied and must ride
            # along or the copy is unreadable.
            metadata = {k: v for k, v in h.items()
                        if k.startswith(AMZ_META_PREFIX)}
            metadata.update({k: v for k, v in fi.metadata.items()
                             if k.startswith("x-mtpu-internal-")})
        from ..crypto import sse
        src_algo = fi.metadata.get(sse.META_ALGO, "")
        try:
            dst_wants_sse = (sse.parse_ssec_key(h) is not None
                             or h.get(sse.H_SSE, "") in ("AES256",
                                                         "aws:kms"))
        except sse.SSEError as e:
            raise S3Error("InvalidArgument", str(e)) from None
        if src_algo or dst_wants_sse:
            # Ciphertext can't be copied verbatim (SSE-C sealing keys
            # are bound to the source path; a dest SSE request needs a
            # fresh seal), so run the full decrypt -> re-encrypt cycle
            # (cf. CopyObject SSE handling, cmd/object-handlers.go
            # CopyObjectHandler).  The SSE-C source key arrives in
            # x-amz-copy-source-...-customer-* headers.
            src_h = {
                sse.H_SSEC_ALGO: h.get(
                    "x-amz-copy-source-server-side-encryption-"
                    "customer-algorithm", ""),
                sse.H_SSEC_KEY: h.get(
                    "x-amz-copy-source-server-side-encryption-"
                    "customer-key", ""),
                sse.H_SSEC_MD5: h.get(
                    "x-amz-copy-source-server-side-encryption-"
                    "customer-key-md5", ""),
            }
            try:
                data = sse.decrypt_for_get(data, fi.metadata, src_h,
                                           self.kms, src_bucket, src_key)
            except sse.SSEError as e:
                raise S3Error("AccessDenied", str(e)) from None
            for mk in (sse.META_ALGO, sse.META_KEY_MD5, sse.META_SSEC_IV,
                       sse.META_KMS_KEY_ID, sse.META_SEALED_KEY,
                       sse.META_ACTUAL_SIZE):
                metadata.pop(mk, None)
            eff_h = dict(h)
            if src_algo == "SSE-S3" and not dst_wants_sse:
                # AWS preserves SSE-S3 across copies unless the request
                # says otherwise.
                eff_h[sse.H_SSE] = "AES256"
            stored_plain_len = len(data)   # post-compression plaintext
            try:
                data, su = sse.encrypt_for_put(data, eff_h, self.kms,
                                               bucket, key)
            except sse.SSEError as e:
                raise S3Error("InvalidArgument", str(e)) from None
            metadata.update(su)
            compressed = bool(metadata.get("x-mtpu-internal-compression"))
            if su and not compressed:
                # client size = pre-seal length (sealing inflates the
                # stored bytes; GET must announce the plaintext size)
                metadata[self.CLIENT_SIZE_KEY] = str(stored_plain_len)
            elif not su and not compressed:
                metadata.pop(self.CLIENT_SIZE_KEY, None)
        versioned = self.bucket_versioning_enabled(bucket)
        # Storage class: an explicit request header re-classes the copy;
        # otherwise the source's class (already riding in metadata)
        # keeps its parity (cf. CopyObject storage-class handling,
        # cmd/object-handlers.go).
        if self.SC_HEADER in h:
            metadata.pop(self.SC_HEADER, None)
            parity = self._parity_for_request(h, metadata)
        elif self.SC_HEADER in metadata:
            parity = self.config_sys.parity_for_class(
                self.STORAGE_CLASSES.get(metadata[self.SC_HEADER],
                                         "standard"))
        else:
            parity = None
        try:
            out = self.pools.put_object(bucket, key, data, metadata=metadata,
                                        versioned=versioned, parity=parity)
        except StorageError as e:
            raise from_storage_error(e) from None
        root = ET.Element("CopyObjectResult", xmlns=S3_NS)
        _el(root, "ETag", f'"{out.metadata.get("etag", "")}"')
        _el(root, "LastModified", _iso(out.mod_time_ns))
        return Response(200, _xml(root), {"Content-Type": "application/xml"})

    def delete_object(self, bucket: str, key: str, query: dict,
                      headers: dict[str, str] | None = None) -> Response:
        version_id = query.get("versionId", [""])[0]
        versioned = self.bucket_versioning_enabled(bucket)
        hl = {k.lower(): v for k, v in (headers or {}).items()}

        # One metadata fetch serves both the WORM check and the tier-free
        # check (only hard deletes — versionId set or unversioned bucket —
        # destroy data; a delete marker keeps the version readable).
        prev = None
        if version_id or not versioned:
            try:
                prev = self.pools.head_object(bucket, key, version_id)
            except StorageError:
                prev = None
        if prev is not None:
            from ..bucket import object_lock as ol
            bypass = hl.get(
                "x-amz-bypass-governance-retention", "") == "true"
            reason = ol.check_delete_allowed(prev.metadata,
                                             bypass_governance=bypass)
            if reason:
                raise S3Error("ObjectLocked", reason)
        tiered_fi = (prev if prev is not None and self.tier_mgr is not None
                     and self.tier_mgr.is_transitioned(prev) else None)

        try:
            dm = self.pools.delete_object(bucket, key, version_id, versioned)
        except StorageError as e:
            err = from_storage_error(e)
            # S3 DELETE of a nonexistent key is a 204 no-op.
            if err.api.code == "NoSuchKey":
                return Response(204)
            raise err from None
        # Only a hard delete frees the tier copy; a delete marker keeps
        # the noncurrent version readable.
        if tiered_fi is not None and dm is None:
            self.tier_mgr.on_version_deleted(tiered_fi)
        self._publish_event(
            "s3:ObjectRemoved:DeleteMarkerCreated" if dm is not None
            else "s3:ObjectRemoved:Delete", bucket, key,
            version_id=version_id)
        # Only a delete of the CURRENT object propagates to replication
        # targets; removing a specific noncurrent version must not take
        # down the target's live copy. A REPLICA-marked delete (sent by
        # a peer's replication worker — the marker is stripped from
        # anyone without s3:ReplicateObject) must not bounce back:
        # active-active delete loop guard, same as the PUT path.
        is_replica_del = (hl.get("x-amz-replication-status")
                          == "REPLICA")
        if self.replication is not None and not version_id \
                and not is_replica_del:
            self.replication.on_delete(
                bucket, key,
                version_id=(dm.version_id or "") if dm is not None
                else "",
                delete_marker=dm is not None)
        h = {}
        if dm is not None and dm.version_id:
            h = {"x-amz-version-id": dm.version_id,
                 "x-amz-delete-marker": "true"}
        return Response(204, headers=h)

    # ---- object tagging / retention / legal hold ---------------------------

    def put_object_tagging(self, bucket: str, key: str, query: dict,
                           body: bytes) -> Response:
        fi = self._head_for_update(bucket, key, query)
        try:
            root = ET.fromstring(body)
        except ET.ParseError:
            raise S3Error("MalformedXML") from None
        for el in root.iter():
            if "}" in el.tag:
                el.tag = el.tag.split("}", 1)[1]
        pairs = []
        for tag_el in root.iter("Tag"):
            k = tag_el.findtext("Key") or ""
            v = tag_el.findtext("Value") or ""
            pairs.append(f"{urllib.parse.quote(k)}={urllib.parse.quote(v)}")
        self._update_metadata(bucket, key, fi,
                              {"x-amz-tagging": "&".join(pairs)})
        return Response(200)

    def get_object_tagging(self, bucket: str, key: str,
                           query: dict) -> Response:
        fi = self._head_for_update(bucket, key, query)
        root = ET.Element("Tagging", xmlns=S3_NS)
        ts = _el(root, "TagSet")
        raw = fi.metadata.get("x-amz-tagging", "")
        if raw:
            for pair in raw.split("&"):
                k, _, v = pair.partition("=")
                te = _el(ts, "Tag")
                _el(te, "Key", urllib.parse.unquote(k))
                _el(te, "Value", urllib.parse.unquote(v))
        return Response(200, _xml(root), {"Content-Type": "application/xml"})

    def put_object_retention(self, bucket: str, key: str, query: dict,
                             body: bytes,
                             headers: dict | None = None) -> Response:
        from ..bucket import object_lock as ol
        fi = self._head_for_update(bucket, key, query)
        try:
            new_meta = ol.parse_retention_xml(body)
        except ET.ParseError:
            raise S3Error("MalformedXML") from None
        if ol._parse_date(new_meta.get(ol.RET_DATE_KEY, "")) is None:
            raise S3Error("InvalidRetentionDate")
        hl = {k.lower(): v for k, v in (headers or {}).items()}
        bypass = hl.get("x-amz-bypass-governance-retention", "") == "true"
        # COMPLIANCE retention can only be extended; GOVERNANCE needs
        # the bypass header to shorten (cf. enforceRetentionBypass).
        if ol.is_retention_active(fi.metadata):
            old_mode = fi.metadata.get(ol.RET_MODE_KEY, "").upper()
            old_until = ol._parse_date(fi.metadata.get(ol.RET_DATE_KEY, ""))
            new_until = ol._parse_date(new_meta[ol.RET_DATE_KEY])
            shrinking = old_until and new_until and new_until < old_until
            if old_mode == "COMPLIANCE" and shrinking:
                raise S3Error("ObjectLocked",
                              "compliance retention cannot be shortened")
            if old_mode == "GOVERNANCE" and shrinking and not bypass:
                raise S3Error("ObjectLocked",
                              "governance retention needs bypass")
        self._update_metadata(bucket, key, fi, new_meta)
        return Response(200)

    def get_object_retention(self, bucket: str, key: str,
                             query: dict) -> Response:
        from ..bucket import object_lock as ol
        fi = self._head_for_update(bucket, key, query)
        if not fi.metadata.get(ol.RET_MODE_KEY):
            raise S3Error("NoSuchObjectLockConfiguration")
        return Response(200, ol.retention_xml(fi.metadata),
                        {"Content-Type": "application/xml"})

    def put_object_legal_hold(self, bucket: str, key: str, query: dict,
                              body: bytes) -> Response:
        from ..bucket import object_lock as ol
        fi = self._head_for_update(bucket, key, query)
        try:
            root = ET.fromstring(body)
        except ET.ParseError:
            raise S3Error("MalformedXML") from None
        status = (root.findtext("Status")
                  or root.findtext(f"{{{S3_NS}}}Status") or "OFF")
        self._update_metadata(bucket, key, fi,
                              {ol.LEGAL_HOLD_KEY: status.upper()})
        return Response(200)

    def get_object_legal_hold(self, bucket: str, key: str,
                              query: dict) -> Response:
        from ..bucket import object_lock as ol
        fi = self._head_for_update(bucket, key, query)
        root = ET.Element("LegalHold", xmlns=S3_NS)
        _el(root, "Status",
            "ON" if ol.is_legal_hold_on(fi.metadata) else "OFF")
        return Response(200, _xml(root), {"Content-Type": "application/xml"})

    def _head_for_update(self, bucket: str, key: str, query: dict):
        version_id = query.get("versionId", [""])[0]
        try:
            return self.pools.head_object(bucket, key, version_id)
        except StorageError as e:
            raise from_storage_error(e) from None

    def _update_metadata(self, bucket: str, key: str, fi,
                         updates: dict) -> None:
        """Merge metadata keys into an existing version in place
        (cf. updateObjectMetadata, cmd/erasure-object.go:1513)."""
        meta = dict(fi.metadata)
        meta.update({k: v for k, v in updates.items() if v})
        for k, v in updates.items():
            if not v:
                meta.pop(k, None)
        fi.metadata = meta
        try:
            self.pools.update_object_metadata(bucket, key, fi)
        except StorageError as e:
            raise from_storage_error(e) from None
        # Metadata-change re-replication (tags/retention/legal-hold,
        # cf. replicateMetadata): the target's copy must pick up the
        # new metadata. Replicas never re-replicate (loop guard).
        if (self.replication is not None
                and meta.get("x-amz-replication-status") != "REPLICA"):
            self.replication.on_metadata(bucket, key)

    def delete_objects(self, bucket: str, body: bytes,
                       can_delete=None) -> Response:
        """POST /bucket?delete — multi-object delete
        (cf. DeleteMultipleObjectsHandler, cmd/bucket-handlers.go).
        `can_delete(key, version_id) -> bool` authorizes each key
        individually — a bucket-level check would bypass object-path
        Deny statements."""
        self.head_bucket(bucket)
        try:
            root = ET.fromstring(body)
        except ET.ParseError:
            raise S3Error("MalformedXML") from None
        quiet = root.findtext("Quiet", "false").lower() == "true" or \
            root.findtext(f"{{{S3_NS}}}Quiet", "false").lower() == "true"
        out = ET.Element("DeleteResult", xmlns=S3_NS)
        versioned = self.bucket_versioning_enabled(bucket)
        for obj in list(root.iter("Object")) + list(
                root.iter(f"{{{S3_NS}}}Object")):
            key = obj.findtext("Key") or obj.findtext(f"{{{S3_NS}}}Key") or ""
            vid = obj.findtext("VersionId") or \
                obj.findtext(f"{{{S3_NS}}}VersionId") or ""
            if can_delete is not None and not can_delete(key, vid):
                ee = _el(out, "Error")
                _el(ee, "Key", key)
                _el(ee, "Code", "AccessDenied")
                _el(ee, "Message", "Access Denied.")
                continue
            try:
                # Route through the single-delete path so object-lock
                # enforcement, events and replication all apply — the
                # bulk path must not be a WORM bypass.
                q = {"versionId": [vid]} if vid else {}
                self.delete_object(bucket, key, q)
                if not quiet:
                    d = _el(out, "Deleted")
                    _el(d, "Key", key)
            except S3Error as err:
                ee = _el(out, "Error")
                _el(ee, "Key", key)
                _el(ee, "Code", err.api.code)
                _el(ee, "Message", err.message)
            except StorageError as e:
                err = from_storage_error(e)
                if err.api.code == "NoSuchKey":
                    if not quiet:
                        d = _el(out, "Deleted")
                        _el(d, "Key", key)
                    continue
                ee = _el(out, "Error")
                _el(ee, "Key", key)
                _el(ee, "Code", err.api.code)
                _el(ee, "Message", err.message)
        return Response(200, _xml(out), {"Content-Type": "application/xml"})

    # ---- multipart --------------------------------------------------------

    def create_multipart(self, bucket: str, key: str,
                         headers: dict[str, str]) -> Response:
        h = {k.lower(): v for k, v in headers.items()}
        metadata = {k: v for k, v in h.items()
                    if k.startswith(AMZ_META_PREFIX)}
        if "content-type" in h:
            metadata["content-type"] = h["content-type"]
        # Storage class fixes the stripe geometry for EVERY part now
        # (cf. newMultipartUpload, cmd/erasure-multipart.go:39).
        parity = self._parity_for_request(h, metadata)
        # Default retention stamps the upload now; the lock/quota gate
        # runs again at complete time when the size is known.
        lock_cfg = self._lock_config(bucket)
        if lock_cfg is not None and lock_cfg.get("enabled"):
            from ..bucket import object_lock as ol
            metadata.update(ol.default_retention_metadata(lock_cfg))
        try:
            upload_id = self.pools.new_multipart_upload(bucket, key,
                                                        metadata=metadata,
                                                        parity=parity)
        except StorageError as e:
            raise from_storage_error(e) from None
        root = ET.Element("InitiateMultipartUploadResult", xmlns=S3_NS)
        _el(root, "Bucket", bucket)
        _el(root, "Key", key)
        _el(root, "UploadId", upload_id)
        return Response(200, _xml(root), {"Content-Type": "application/xml"})

    def put_part(self, bucket: str, key: str, query: dict,
                 body, headers: dict[str, str] | None = None) -> Response:
        upload_id = query.get("uploadId", [""])[0]
        part_number = int(query.get("partNumber", ["0"])[0])
        if not (1 <= part_number <= 10000):
            raise S3Error("InvalidArgument", "part number out of range")
        h = {k.lower(): v for k, v in (headers or {}).items()}
        if "x-amz-copy-source" in h:
            from ..utils import streams
            if streams.is_reader(body):
                # Copy requests carry no meaningful body; drain so the
                # keep-alive socket isn't left desynced (same rule as
                # the CopyObject branch in put_object).
                while body.read(1 << 20):
                    pass
            return self._upload_part_copy(bucket, key, upload_id,
                                          part_number, h)
        try:
            info = self.pools.put_object_part(bucket, key, upload_id,
                                              part_number, body)
        except StorageError as e:
            raise from_storage_error(e) from None
        return Response(200, headers={"ETag": f'"{info.etag}"'})

    def _upload_part_copy(self, bucket: str, key: str, upload_id: str,
                          part_number: int, h: dict[str, str]) -> Response:
        """UploadPartCopy (cf. CopyObjectPartHandler,
        cmd/object-handlers.go): source an upload part from an existing
        object (optionally a byte range of it). The source is read as
        PLAINTEXT — decrypt/decompress applied — because the part joins
        a new EC stream with its own framing/transforms; copied and
        uploaded parts must complete byte-identical."""
        src = urllib.parse.unquote(h["x-amz-copy-source"]).lstrip("/")
        src_bucket, _, src_key = src.partition("/")
        src_vid = ""
        if "?versionId=" in src_key:
            src_key, _, src_vid = src_key.partition("?versionId=")
        if not src_bucket or not src_key:
            raise S3Error("InvalidArgument", "bad x-amz-copy-source")
        src_h = {
            "x-amz-server-side-encryption-customer-algorithm": h.get(
                "x-amz-copy-source-server-side-encryption-"
                "customer-algorithm", ""),
            "x-amz-server-side-encryption-customer-key": h.get(
                "x-amz-copy-source-server-side-encryption-"
                "customer-key", ""),
            "x-amz-server-side-encryption-customer-key-md5": h.get(
                "x-amz-copy-source-server-side-encryption-"
                "customer-key-md5", ""),
        }
        try:
            fi, data = self._read_plaintext(src_bucket, src_key, src_vid,
                                            src_h)
        except StorageError as e:
            raise from_storage_error(e) from None
        rng = h.get("x-amz-copy-source-range", "")
        if rng:
            if not rng.startswith("bytes="):
                raise S3Error("InvalidArgument",
                              "x-amz-copy-source-range must be bytes=")
            start_s, _, end_s = rng[len("bytes="):].partition("-")
            try:
                start = int(start_s)
                end = int(end_s) if end_s else len(data) - 1
            except ValueError:
                raise S3Error("InvalidArgument", rng) from None
            # UploadPartCopy ranges are strict: both ends must lie
            # inside the source object (unlike GET's RFC 7233 clamping).
            if start < 0 or end < start or end >= len(data):
                raise S3Error("InvalidRange", rng)
            data = memoryview(data)[start:end + 1]
        try:
            info = self.pools.put_object_part(bucket, key, upload_id,
                                              part_number, bytes(data))
        except StorageError as e:
            raise from_storage_error(e) from None
        root = ET.Element("CopyPartResult", xmlns=S3_NS)
        _el(root, "ETag", f'"{info.etag}"')
        _el(root, "LastModified", _iso(time.time_ns()))
        return Response(200, _xml(root),
                        {"Content-Type": "application/xml"})

    def complete_multipart(self, bucket: str, key: str, query: dict,
                           body: bytes) -> Response:
        upload_id = query.get("uploadId", [""])[0]
        try:
            root = ET.fromstring(body)
        except ET.ParseError:
            raise S3Error("MalformedXML") from None
        parts = []
        for p in list(root.iter("Part")) + list(root.iter(f"{{{S3_NS}}}Part")):
            num = p.findtext("PartNumber") or \
                p.findtext(f"{{{S3_NS}}}PartNumber")
            etag = (p.findtext("ETag") or p.findtext(f"{{{S3_NS}}}ETag")
                    or "").strip('"')
            parts.append((int(num), etag))
        versioned = self.bucket_versioning_enabled(bucket)

        # Same write-path gates as put_object — multipart must not be a
        # quota/WORM bypass (the reference runs these in
        # CompleteMultipartUploadHandler too).
        try:
            stored = {p.number: p
                      for p in self.pools.list_parts(bucket, key,
                                                     upload_id)}
        except StorageError as e:
            raise from_storage_error(e) from None
        total = sum(stored[n].size for n, _ in parts if n in stored)
        quota_raw = self.meta.get(bucket, "quota")
        if quota_raw is not None:
            from ..bucket import quota as bq
            reason = bq.check_quota(self.pools, bucket, total,
                                    bq.parse_quota_config(quota_raw),
                                    self.scanner)
            if reason:
                raise S3Error("QuotaExceeded", reason)
        lock_cfg = self._lock_config(bucket)
        if lock_cfg is not None and lock_cfg.get("enabled") \
                and not versioned:
            from ..bucket import object_lock as ol
            try:
                prev = self.pools.head_object(bucket, key)
                reason = ol.check_delete_allowed(prev.metadata)
                if reason:
                    raise S3Error("ObjectLocked", reason)
            except StorageError:
                pass

        try:
            with _span("engine.complete_multipart"):
                fi = self.pools.complete_multipart_upload(
                    bucket, key, upload_id, parts, versioned=versioned)
        except StorageError as e:
            raise from_storage_error(e) from None
        etag = fi.metadata.get("etag", "")
        self._publish_event(
            "s3:ObjectCreated:CompleteMultipartUpload", bucket, key,
            size=fi.size, etag=etag, version_id=fi.version_id)
        if self.replication is not None:
            self.replication.on_put(bucket, key)
        root = ET.Element("CompleteMultipartUploadResult", xmlns=S3_NS)
        _el(root, "Bucket", bucket)
        _el(root, "Key", key)
        _el(root, "ETag", f'"{etag}"')
        return Response(200, _xml(root), {"Content-Type": "application/xml"})

    def abort_multipart(self, bucket: str, key: str, query: dict) -> Response:
        upload_id = query.get("uploadId", [""])[0]
        try:
            self.pools.abort_multipart_upload(bucket, key, upload_id)
        except StorageError as e:
            raise from_storage_error(e) from None
        return Response(204)

    def list_parts(self, bucket: str, key: str, query: dict) -> Response:
        upload_id = query.get("uploadId", [""])[0]
        try:
            parts = self.pools.list_parts(bucket, key, upload_id)
        except StorageError as e:
            raise from_storage_error(e) from None
        root = ET.Element("ListPartsResult", xmlns=S3_NS)
        _el(root, "Bucket", bucket)
        _el(root, "Key", key)
        _el(root, "UploadId", upload_id)
        _el(root, "IsTruncated", "false")
        for p in parts:
            pe = _el(root, "Part")
            _el(pe, "PartNumber", p.number)
            _el(pe, "ETag", f'"{p.etag}"')
            _el(pe, "Size", p.size)
        return Response(200, _xml(root), {"Content-Type": "application/xml"})

    def list_multipart_uploads(self, bucket: str, query: dict) -> Response:
        prefix = query.get("prefix", [""])[0]
        self.head_bucket(bucket)
        uploads = self.pools.list_multipart_uploads(bucket, prefix)
        root = ET.Element("ListMultipartUploadsResult", xmlns=S3_NS)
        _el(root, "Bucket", bucket)
        _el(root, "Prefix", prefix)
        _el(root, "IsTruncated", "false")
        for u in uploads:
            ue = _el(root, "Upload")
            _el(ue, "Key", u["object"])
            _el(ue, "UploadId", u["upload_id"])
        return Response(200, _xml(root), {"Content-Type": "application/xml"})
