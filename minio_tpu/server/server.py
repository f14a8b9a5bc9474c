"""The S3 HTTP server: routing, middleware, auth dispatch.

Equivalent of the reference's internal/http server + cmd/routers.go:82
(configureServerHandler) + cmd/auth-handler.go:281 (checkRequestAuthType):
a threading HTTP server whose single dispatch point classifies the request
(anonymous / presigned / header-signed / streaming-signed), verifies
SigV4, then routes on (method, path shape, query) the way
cmd/api-router.go:175 registers gorilla-mux routes.

Middleware checks (time validity, size limits, reserved-metadata filter)
happen inline before dispatch, mirroring cmd/generic-handlers.go.
"""

from __future__ import annotations

import faulthandler
import os as _os
import secrets
import socket
import ssl as _ssl
import sys
import threading
import time
import traceback
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from ..engine.pools import ServerPools
from ..observe import span as ospan
from ..observe.metrics import DATA_PATH
from ..ops import zerocopy as zc
from ..storage.errors import StorageError
from ..utils import streams
from . import qos as _qos
from .api_errors import S3Error
from .handlers import Response, S3Handlers, error_response
from .sigv4 import (STREAMING_PAYLOAD, UNSIGNED_PAYLOAD, Credentials,
                    StreamingSigV4Reader, decode_streaming_body,
                    verify_header_signature, verify_presigned)

MAX_HEADER_BODY = 5 * 1024 ** 3      # max single PUT (5 GiB part limit)


def _api_name(method: str, path: str, query: dict, headers) -> str:
    """S3/admin API name for the request's root span — the per-API key
    traces aggregate under (the role of api-router.go handler names in
    the reference's trace/metrics labels). Best-effort: unrecognized
    shapes fall back to method-qualified names rather than guessing."""
    if path.startswith("/minio/admin/"):
        # version prefixes v1/v3 are the same length — same strip
        # _dispatch_admin uses.
        sub = path[len("/minio/admin/v1/"):].strip("/")
        return "admin." + ((sub.split("/", 1)[0] or "Service"))
    if path.startswith("/minio/"):
        if path == "/minio/listen":
            return "api.ListenNotification"
        return "internal." + path[len("/minio/"):].strip("/").replace(
            "/", ".")
    parts = path.strip("/").split("/", 1)
    bucket = parts[0]
    key = parts[1] if len(parts) > 1 else ""
    if not bucket:
        return "api.ListBuckets" if method == "GET" else f"api.{method}Root"
    if key:
        if method == "GET":
            return ("api.ListParts" if "uploadId" in query
                    else "api.GetObject")
        if method == "HEAD":
            return "api.HeadObject"
        if method == "PUT":
            if "partNumber" in query and "uploadId" in query:
                return ("api.UploadPartCopy"
                        if "x-amz-copy-source" in headers
                        else "api.UploadPart")
            if "x-amz-copy-source" in headers:
                return "api.CopyObject"
            return "api.PutObject"
        if method == "POST":
            if "uploads" in query:
                return "api.NewMultipartUpload"
            if "uploadId" in query:
                return "api.CompleteMultipartUpload"
            return f"api.{method}Object"
        if method == "DELETE":
            return ("api.AbortMultipartUpload" if "uploadId" in query
                    else "api.DeleteObject")
        return f"api.{method}Object"
    if method == "GET":
        if "events" in query:
            return "api.ListenNotification"
        if "location" in query:
            return "api.GetBucketLocation"
        if "uploads" in query:
            return "api.ListMultipartUploads"
        if "versions" in query:
            return "api.ListObjectVersions"
        return "api.ListObjects"
    if method == "HEAD":
        return "api.HeadBucket"
    if method == "PUT":
        return "api.PutBucket" if not query else "api.PutBucketConfig"
    if method == "DELETE":
        return ("api.DeleteBucket" if not query
                else "api.DeleteBucketConfig")
    if method == "POST" and "delete" in query:
        return "api.DeleteMultipleObjects"
    return f"api.{method}Bucket"


class S3Server:
    """Owns the object layer, creds and the HTTP plumbing."""

    def __init__(self, pools: ServerPools | None, creds: Credentials,
                 host: str = "127.0.0.1", port: int = 0,
                 trace_sink=None, iam=None, notify=None,
                 replication=None, scanner=None, kms=None,
                 compress_enabled: bool = False, tier_mgr=None,
                 oidc=None, certs: tuple[str, str] | None = None,
                 rpc_router=None, site_replicator=None,
                 ldap=None, client_ca: str | None = None,
                 bucket_dns=None, reuse_port: bool = False,
                 worker_plane=None, worker_id: int | None = None):
        self.oidc = oidc                   # iam.oidc.OpenIDConfig | None
        self.ldap = ldap                   # iam.ldap.LDAPConfig | None
        self.client_ca = client_ca         # CA bundle for mTLS STS
        self.site_replicator = site_replicator   # SiteReplicator | None
        self.pools = pools
        self.creds = creds                 # root credentials (policy bypass)
        self.iam = iam                     # IAMSys | None
        # Inter-node RPC planes mount under the S3 port (the reference
        # serves storage/peer/lock REST on the main server port too,
        # routed by path prefix — cmd/routers.go:27-39). pools may be
        # None during cluster boot: the front door must be up so peers
        # can reach OUR storage plane while WE wait for format quorum;
        # S3 requests get 503 ServerNotInitialized until
        # bind_object_layer() installs the engine.
        self.rpc_router = rpc_router
        # Cluster back-reference (set by boot_cluster_node): admin-info
        # and /metrics read per-peer liveness through it.
        self.cluster_node = None
        self._handler_opts = dict(notify=notify, replication=replication,
                                  scanner=scanner, kms=kms,
                                  compress_enabled=compress_enabled,
                                  tier_mgr=tier_mgr,
                                  bucket_dns=bucket_dns)
        self.bucket_dns = bucket_dns
        self.handlers = (S3Handlers(pools, **self._handler_opts)
                         if pools is not None else None)
        if scanner is not None and self.handlers is not None \
                and hasattr(scanner, "attach_config"):
            # scan cycles run ILM expiry/transitions against the live
            # bucket-config store (free-version semantics included)
            scanner.attach_config(self.handlers.meta,
                                  self.handlers.tier_mgr)

        self.trace_sink = trace_sink
        from ..observe.logger import Logger, RingTarget
        from ..observe.metrics import MetricsRegistry
        self.metrics = MetricsRegistry()
        self.log = Logger()
        self.log_ring = RingTarget()
        self.log.add_target(self.log_ring)
        if notify is not None and self.handlers is not None:
            # after the logger exists: a bad notify config is logged,
            # never boot-fatal
            self._register_config_targets(notify)
        self._reload_replication()
        # Structured audit plane (observe/audit.py): targets built from
        # MTPU_AUDIT at boot.  A typo'd target spec raises and refuses
        # to serve — a silent fallback would silently lose the trail.
        from ..observe.audit import targets_from_env
        self.audit_targets: list = targets_from_env()
        # Sliding SLO window feed (observe/lastminute.py).  MTPU_SLO=0
        # is the kill switch the <3% request-overhead guard compares
        # against.
        self.slo_enabled = _os.environ.get("MTPU_SLO", "1") != "0"
        self.scanner = scanner
        self.config = None                 # lazy ConfigSys (admin API)
        self.service_event = ""            # "" | "restart" | "stop"
        # Graceful-drain plane (cmd/signals.go role): once draining,
        # new S3 requests bounce with 503 + Retry-After while inflight
        # ones finish.  The counter is ours, not metrics.inflight —
        # that gauge closes before the response body is written, and a
        # drain must wait for the LAST BYTE of every streamed GET.
        self.draining = False
        # Boot asked for the lanes' shape ladder and it is still being
        # built: requests are served (at the next larger step), the
        # readiness probe waits (see build_ladders); `_asking`: boot is
        # still deciding what to ask for, so an idle build thread says
        # nothing yet.
        self.warming = False
        self._asking = False
        self._inflight = 0
        self._drain_cv = threading.Condition()
        # What the stall watcher reads (see `_watch_stalls`): when each
        # request in flight began, by its thread (a stream that ends
        # only when its client hangs up takes itself out), when a
        # request last completed or a streamed response last handed a
        # chunk to its socket.
        self._began: dict[int, float] = {}
        self._last_progress = time.monotonic()
        self._stall_stop = threading.Event()
        # Overload plane (server/qos.py): the process-tree singleton —
        # in pool mode WorkerPlane already created it BEFORE the fork,
        # so this reference is the SAME fork-shared mapping in every
        # worker (one global admission cap, not N local ones).
        self.qos = _qos.get_plane()
        #: Per-bucket bandwidth budgets from the quota config, cached
        #: briefly so the admission path never does a metadata read
        #: per request.  {bucket: (rate_bytes_per_s, stamp)}
        self._qos_bw_cache: dict = {}
        # Pre-fork pool wiring (server/workers.py): every worker binds
        # the same port via SO_REUSEPORT; the plane carries the shared
        # control block whose slabs feed /metrics and admin-info.
        self.worker_plane = worker_plane
        self.worker_id = worker_id
        # How a streamed body's pulls leave Python (_body_reader): built
        # and loaded here, at boot, never on a request's thread.
        self._recv_exact = streams.native_recv_exact()
        # Site-hook single-flight state is created EAGERLY: the lazy
        # `if getattr(...) is None: self._site_hook_mu = Lock()` dance
        # raced — two first-ever mutations on different handler threads
        # could each install their own lock and both start a reconcile
        # worker.
        self._site_hook_mu = threading.Lock()
        self._site_hook_busy = False
        self._site_hook_again = False
        outer = self

        class _Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            server_version = "MinioTPU"
            # Per-connection socket timeout (StreamRequestHandler.setup
            # applies it): a client that stalls mid-body for this long
            # surfaces as TimeoutError in the dispatch below and maps
            # to a clean RequestTimeout, not a raw traceback.
            timeout = float(_os.environ.get("MTPU_SOCKET_TIMEOUT",
                                            "60") or 60)

            def log_message(self, fmt, *args):  # quiet; tracing has its own
                pass

            def _respond(self, resp: Response):
                body = resp.body or b""
                chunked = resp.headers.get(
                    "Transfer-Encoding") == "chunked"
                # Zero-copy writer gate: plain TCP only (SSLSocket's
                # sendmsg raises NotImplementedError and sendfile
                # can't cross the record layer) and never for chunked
                # framing (chunk headers interleave the body).
                use_zc = (zc.zerocopy_enabled() and not chunked
                          and not isinstance(self.connection,
                                             _ssl.SSLSocket))
                if resp.body_file is not None and not use_zc:
                    # TLS / oracle leg: materialize the verified plans
                    # through userspace — byte-identical to the sends.
                    try:
                        if self.command != "HEAD":
                            body = b"".join(p.read_all()
                                            for p in resp.body_file)
                    finally:
                        for p in resp.body_file:
                            p.close()
                    resp.body_file = None
                    DATA_PATH.record_zerocopy_fallback()
                self.send_response(resp.status)
                for k, v in resp.headers.items():
                    self.send_header(k, v)
                if "Content-Length" not in resp.headers and not chunked:
                    self.send_header("Content-Length", str(len(body)))
                self.send_header("x-amz-request-id", self.request_id)
                # security headers on every response (the
                # addSecurityHeaders middleware, cmd/generic-handlers.go)
                self.send_header("X-Content-Type-Options", "nosniff")
                self.send_header("X-XSS-Protection", "1; mode=block")
                self.send_header("Content-Security-Policy",
                                 "block-all-mixed-content")
                if use_zc:
                    # Steal the block end_headers() would flush: the
                    # header bytes are built by the SAME send_response/
                    # send_header calls as the buffered path, then
                    # leave coalesced with the first body segment in
                    # one sendmsg (or ahead of the sendfile runs) —
                    # byte-identical on the wire, 1-2 syscalls total.
                    self._headers_buffer.append(b"\r\n")
                    hdr = b"".join(self._headers_buffer)
                    self._headers_buffer = []
                    sock = self.connection
                    if self.command == "HEAD":
                        zc.send_gather(sock, (hdr,))
                        return
                    if resp.body_file is not None:
                        try:
                            zc.send_gather(sock, (hdr,))
                            n = 0
                            for p in resp.body_file:
                                n += zc.send_file(sock, p.fd, p.runs)
                            DATA_PATH.record_zerocopy_send("sendfile",
                                                           n)
                        finally:
                            for p in resp.body_file:
                                p.close()
                        return
                    if resp.body_iter is not None:
                        segs = [hdr]
                        it = iter(resp.body_iter)
                        first = next(it, None)
                        if first is not None and len(first):
                            segs.append(first)
                        n = zc.send_gather(sock, segs) - len(hdr)
                        for chunk in it:
                            if len(chunk):
                                n += zc.send_gather(sock, (chunk,))
                        DATA_PATH.record_zerocopy_send("sendmsg", n)
                        return
                    n = zc.send_gather(sock, (hdr, body)) - len(hdr)
                    DATA_PATH.record_zerocopy_send("sendmsg",
                                                   max(0, n))
                    return
                self.end_headers()
                if self.command == "HEAD":
                    return
                if resp.body_iter is not None:
                    # Streamed body: chunks flow socket-ward as they
                    # decode; a mid-stream failure can only sever the
                    # connection (headers are gone), same as the
                    # reference once the response has begun. With
                    # Transfer-Encoding: chunked (the admin trace /
                    # listen streams, unknown total length) each chunk
                    # gets HTTP/1.1 chunked framing and the connection
                    # stays reusable after the terminal chunk.
                    if chunked:
                        try:
                            for chunk in resp.body_iter:
                                if len(chunk):
                                    self.wfile.write(
                                        b"%x\r\n" % len(chunk)
                                        + bytes(chunk) + b"\r\n")
                                    self.wfile.flush()
                            self.wfile.write(b"0\r\n\r\n")
                        except (BrokenPipeError, ConnectionResetError):
                            # Stream consumer hung up mid-flight: close
                            # the generator (runs its unsubscribe
                            # cleanup) and drop the connection.
                            close = getattr(resp.body_iter, "close",
                                            None)
                            if close is not None:
                                close()
                            self.close_connection = True
                    else:
                        # len() not truthiness: chunks may be ndarray
                        # views (hot-cache zero-copy) whose bool() is
                        # ambiguous; write() takes any buffer.
                        for chunk in resp.body_iter:
                            if len(chunk):
                                self.wfile.write(chunk)
                elif len(body):
                    self.wfile.write(body)

            def _handle(self):
                # Drain gate + inflight tracking around the WHOLE
                # request (dispatch and response write): drain() blocks
                # on this counter reaching zero, so a SIGTERM never
                # severs a response mid-stream.
                parsed = urllib.parse.urlsplit(self.path)
                path = urllib.parse.unquote(parsed.path)
                if outer.draining and not path.startswith(
                        ("/minio/health/", "/minio/rpc/")):
                    self.request_id = secrets.token_hex(8)
                    resp = error_response(
                        S3Error("ServiceUnavailable",
                                "server is draining for shutdown"),
                        path, self.request_id)
                    resp.headers["Retry-After"] = "1"
                    self.close_connection = True
                    # Drain bounces never reach _handle_inner's audit
                    # point, but the trail must still show them.
                    outer._emit_audit(
                        api=_api_name(self.command, path, {},
                                      self.headers),
                        method=self.command, path=path, status=503,
                        error_code="ServiceUnavailable",
                        source_ip=self.client_address[0],
                        request_id=self.request_id)
                    try:
                        self._respond(resp)
                    except (BrokenPipeError, ConnectionResetError,
                            TimeoutError):
                        pass
                    return
                # Admission control (server/qos.py): one fork-shared
                # requests-max semaphore with a deadline queue.  Same
                # exemptions as the drain gate plus the admin/metrics
                # planes — an operator must be able to see and steer a
                # saturated server (cmd/handler-api.go maxClients
                # exempts its health endpoints the same way).
                qos_slot = False
                if _qos.qos_enabled() and not path.startswith(
                        ("/minio/health/", "/minio/rpc/",
                         "/minio/admin/", "/minio/v2/metrics",
                         "/minio/listen")):
                    klass = _qos.tenant_class(
                        _qos.peek_access_key(self.headers))
                    verdict, waited = outer.qos.acquire(klass)
                    if verdict != "ok":
                        self.request_id = secrets.token_hex(8)
                        api_name = _api_name(self.command, path, {},
                                             self.headers)
                        resp = error_response(
                            S3Error("SlowDown",
                                    "server is at capacity; request "
                                    "shed by admission control"),
                            path, self.request_id)
                        resp.headers["Retry-After"] = "1"
                        self.close_connection = True
                        # Sheds are their own SLO class (≠ errors) and
                        # still leave an audit trail, like drain 503s.
                        if outer.slo_enabled:
                            outer.metrics.observe_api(
                                api_name, waited, shed=True)
                        outer._emit_audit(
                            api=api_name, method=self.command,
                            path=path, status=503,
                            error_code="SlowDown",
                            source_ip=self.client_address[0],
                            request_id=self.request_id,
                            duration_ms=waited * 1e3)
                        try:
                            self._respond(resp)
                        except (BrokenPipeError, ConnectionResetError,
                                TimeoutError):
                            pass
                        return
                    qos_slot = True
                with outer._drain_cv:
                    outer._inflight += 1
                outer._began[threading.get_ident()] = time.monotonic()
                if outer.worker_plane is not None:
                    outer.worker_plane.state.note_request(
                        outer.worker_id)
                try:
                    self._handle_inner()
                finally:
                    if qos_slot:
                        outer.qos.release()
                    outer._began.pop(threading.get_ident(), None)
                    outer._last_progress = time.monotonic()
                    with outer._drain_cv:
                        outer._inflight -= 1
                        outer._drain_cv.notify_all()

            def _handle_inner(self):
                import time as _time
                self.request_id = secrets.token_hex(8)
                parsed = urllib.parse.urlsplit(self.path)
                path = urllib.parse.unquote(parsed.path)
                query = urllib.parse.parse_qs(parsed.query,
                                              keep_blank_values=True)
                if path == "/crossdomain.xml":
                    # setCrossDomainPolicy (cmd/crossdomain-xml-handler.go)
                    body = (b'<?xml version="1.0"?><!DOCTYPE cross-domain-'
                            b'policy SYSTEM "http://www.adobe.com/xml/dtds'
                            b'/cross-domain-policy.dtd"><cross-domain-'
                            b'policy><allow-access-from domain="*" '
                            b'secure="false" /></cross-domain-policy>')
                    self._respond(Response(200, body,
                                           {"Content-Type":
                                            "application/xml"}))
                    return
                if path.startswith("/minio/rpc/") and \
                        outer.rpc_router is not None:
                    # Inter-node plane: bearer-token auth + msgpack,
                    # handled by the router — no S3 middleware, no
                    # S3 signature (cf. storageRESTServer auth,
                    # cmd/storage-rest-server.go).
                    length = int(self.headers.get("Content-Length",
                                                  0) or 0)
                    body = self.rfile.read(length) if length else b""
                    status, out = outer.rpc_router.handle(
                        path, self.headers.get("Authorization", ""),
                        body)
                    self._respond(Response(
                        status, out,
                        {"Content-Type": "application/msgpack"}))
                    return
                t0 = _time.perf_counter()
                outer.metrics.inflight.inc(1)
                # Per-request deadline budget (MTPU_RPC_DEADLINE_MS):
                # armed here, consumed by every storage/lock RPC this
                # request fans out to (rest.py clamps each hop's
                # timeout to the remaining budget; span.wrap_ctx
                # carries it across pool threads).
                from ..rpc import rest as _rest
                _dl_ms = _rest.request_deadline_ms()
                _dl_token = (_rest.set_deadline(_dl_ms / 1000.0)
                             if _dl_ms > 0 else None)
                # Root span: one per request, open through dispatch AND
                # the response write (a streamed GET does its engine
                # reads inside _respond). NOOP unless someone is
                # tracing (ring configured or live trace subscriber).
                api_name = _api_name(self.command, path, query,
                                     self.headers)
                rspan = ospan.TRACER.root(
                    api_name, method=self.command, path=path,
                    request_id=self.request_id)
                rspan.__enter__()
                # Audit identity/routing facts for THIS request.  Reset
                # here because handler instances persist across
                # keep-alive requests; _dispatch stamps them once auth
                # succeeds and routing begins.
                self.audit_access_key = ""
                self.audit_dispatched = False
                err_code = None
                try:
                    if outer.handlers is None and \
                            not path.startswith("/minio/health/"):
                        raise S3Error("ServerNotInitialized")
                    if path.startswith("/minio/admin/") or \
                            path == "/minio/listen":
                        resp = outer._dispatch(self, path, query)
                    elif path.startswith("/minio/"):
                        resp = outer._dispatch_internal(self, path, query)
                    else:
                        resp = outer._dispatch(self, path, query)
                except S3Error as e:
                    err_code = e.api.code
                    resp = error_response(e, path, self.request_id)
                    if err_code == "SlowDown":
                        # Throttle 503s (tenant/bucket token buckets)
                        # carry the same retry hint as admission sheds.
                        resp.headers["Retry-After"] = "1"
                    # A failed request may leave unread body bytes on
                    # the socket (streaming PUTs); don't reuse it.
                    self.close_connection = True
                except streams.StreamError as e:
                    # Malformed/truncated request body: 400-class, not
                    # a handler crash.
                    err_code = "IncompleteBody"
                    resp = error_response(
                        S3Error("IncompleteBody", str(e)), path,
                        self.request_id)
                    self.close_connection = True
                except TimeoutError:
                    # Client stalled mid-body past the socket timeout:
                    # a clean RequestTimeout + connection close, not an
                    # unhandled socket.timeout traceback.
                    err_code = "RequestTimeout"
                    resp = error_response(
                        S3Error("RequestTimeout",
                                "client read timed out mid-request"),
                        path, self.request_id)
                    self.close_connection = True
                except (BrokenPipeError, ConnectionResetError):
                    # Client went away mid-body: nothing to tell them.
                    err_code = "ClientDisconnected"
                    resp = Response(499, b"")
                    self.close_connection = True
                except Exception as e:  # noqa: BLE001
                    outer.log.error(f"handler crash: {e}",
                                    path=path, request_id=self.request_id)
                    err_code = "InternalError"
                    resp = error_response(
                        S3Error("InternalError",
                                f"{type(e).__name__}: {e}"),
                        path, self.request_id)
                    self.close_connection = True
                finally:
                    if _dl_token is not None:
                        _rest.clear_deadline(_dl_token)
                    outer.metrics.inflight.inc(-1)
                # Site replication: successful BUCKET-level mutations
                # (create/delete/config) fan out like IAM ones —
                # internal pushes carry x-mtpu-sr-internal and don't
                # re-enter.
                if (self.command in ("PUT", "DELETE")
                        and resp.status < 300
                        and not path.startswith("/minio/")
                        and "/" not in path.strip("/")
                        and path.strip("/")
                        and not self.headers.get("x-mtpu-sr-internal")):
                    kind = ("bucket-delete"
                            if self.command == "DELETE" and not query
                            else "bucket")
                    try:
                        outer._site_hook(kind,
                                         bucket=path.strip("/"))
                    except Exception:  # noqa: BLE001
                        pass
                dur = (_time.perf_counter() - t0)
                resp_size = (int(resp.headers.get("Content-Length", 0) or 0)
                             if resp.body_iter is not None
                             else len(resp.body or b""))
                # Only successful requests feed the bandwidth monitor:
                # unauthenticated probes of made-up bucket names must
                # not mint tracking state.
                req_bucket = ("" if path.startswith("/minio/")
                              or resp.status >= 400
                              else path.split("/", 2)[1]
                              if path.count("/") >= 1 else "")
                outer.metrics.observe_request(
                    self.command, resp.status, dur,
                    int(self.headers.get("Content-Length", 0) or 0),
                    resp_size, bucket=req_bucket)
                # Post-paid bandwidth accounting: tenant and bucket
                # buckets run a bounded debt (a GET's size is unknown
                # at admission), repaid before the next admit.  Both
                # charges short-circuit unless a rate is configured.
                if _qos.qos_enabled() and resp.status < 400:
                    nbytes = resp_size + int(
                        self.headers.get("Content-Length", 0) or 0)
                    ak = getattr(self, "audit_access_key", "")
                    if ak:
                        outer.qos.charge_tenant_bw(
                            ak, _qos.tenant_class(ak), nbytes)
                    if req_bucket:
                        outer.qos.charge_bucket_bw(
                            req_bucket,
                            outer._qos_bucket_rate(req_bucket), nbytes)
                if outer.slo_enabled:
                    outer.metrics.observe_api(api_name, dur,
                                              error=resp.status >= 400,
                                              nbytes=resp_size)
                sb = ("" if path.startswith("/minio/")
                      else path.lstrip("/"))
                if rspan is not ospan.NOOP:
                    # What the admin trace stream's flat line carries
                    # (ospan.flat), beside the tree's own tags.
                    rspan.tag(status=resp.status,
                              bucket=sb.split("/", 1)[0],
                              object=(sb.split("/", 1)[1]
                                      if "/" in sb else ""),
                              error=resp.status >= 400,
                              request_size=int(self.headers.get(
                                  "Content-Length", 0) or 0),
                              response_size=resp_size,
                              source_ip=self.client_address[0])
                try:
                    if resp.status != 499:
                        if resp.body_iter is not None \
                                and "Transfer-Encoding" not in resp.headers:
                            resp.body_iter = outer._marking_progress(
                                resp.body_iter)
                        with ospan.span("http.respond"):
                            self._respond(resp)
                except (BrokenPipeError, ConnectionResetError,
                        TimeoutError):
                    self.close_connection = True
                finally:
                    # Close the root span BEFORE building the audit
                    # entry so its per-stage timings (flatten of the
                    # child spans) cover the response write too.
                    rspan.__exit__(None, None, None)
                    if outer.audit_targets:
                        stages = None
                        if rspan is not ospan.NOOP:
                            try:
                                stages = ospan.flatten(rspan.to_dict())
                            except Exception:  # noqa: BLE001
                                stages = None
                        obj = (sb.split("/", 1)[1]
                               if "/" in sb else "") or None
                        if (not getattr(self, "audit_dispatched", False)
                                or err_code == "IncompleteBody"):
                            # Rejected before (or during) routing —
                            # auth failure, malformed framing: the
                            # object was never resolved, so the entry
                            # carries a null object.
                            obj = None
                        outer._emit_audit(
                            api=api_name, method=self.command,
                            path=path, status=resp.status,
                            error_code=err_code,
                            bucket=sb.split("/", 1)[0] or None,
                            object_name=obj,
                            access_key=getattr(self,
                                               "audit_access_key", ""),
                            source_ip=self.client_address[0],
                            request_id=self.request_id,
                            rx=int(self.headers.get("Content-Length",
                                                    0) or 0),
                            tx=resp_size, duration_ms=dur * 1e3,
                            stages=stages)

            do_GET = do_PUT = do_POST = do_DELETE = do_HEAD = _handle

        class _TLSThreadingHTTPServer(ThreadingHTTPServer):
            """TLS handshakes run in the per-connection WORKER thread —
            wrapping the listening socket would park the accept loop in
            a blocking handshake, letting one silent client stall the
            whole endpoint."""
            ssl_context = None

            def server_bind(self):
                if reuse_port:
                    # Pre-fork pool: every worker binds the SAME
                    # (host, port) and the kernel spreads connections
                    # across them.  Must be set before bind();
                    # socketserver on 3.10 has no allow_reuse_port.
                    self.socket.setsockopt(socket.SOL_SOCKET,
                                           socket.SO_REUSEPORT, 1)
                super().server_bind()

            def finish_request(self, request, client_address):
                if self.ssl_context is not None:
                    import ssl as _ssl
                    request.settimeout(10)       # bound the handshake
                    try:
                        request = self.ssl_context.wrap_socket(
                            request, server_side=True)
                        request.settimeout(60)
                    except (_ssl.SSLError, OSError):
                        try:
                            request.close()
                        except OSError:
                            pass
                        return
                    try:
                        super().finish_request(request, client_address)
                    finally:
                        # shutdown_request() operates on the ORIGINAL
                        # socket (detached by wrap_socket); close the
                        # TLS socket here so close_notify is sent.
                        try:
                            request.close()
                        except OSError:
                            pass
                    return
                super().finish_request(request, client_address)

        self._httpd = _TLSThreadingHTTPServer((host, port), _Handler)
        self.tls = certs is not None
        if certs is not None:
            # HTTPS front door (the reference serves S3 and all three
            # RPC planes over TLS; internal/http server + certs dir).
            import ssl
            cert_file, key_file = certs
            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(cert_file, key_file)
            if client_ca:
                # mTLS for AssumeRoleWithCertificate: clients MAY
                # present a certificate; those that do are verified
                # against this CA and their CN names their policy.
                ctx.load_verify_locations(client_ca)
                ctx.verify_mode = ssl.CERT_OPTIONAL
            self._httpd.ssl_context = ctx
        self.port = self._httpd.server_port
        self.host = host
        self._thread: threading.Thread | None = None

    # -- lifecycle -----------------------------------------------------------

    def bind_object_layer(self, pools: ServerPools, iam=None,
                          scanner=None) -> None:
        """Install the engine after boot (cluster mode: the listener is
        up first so peers can reach our RPC planes during format wait;
        cf. newObjectLayer assignment, cmd/server-main.go:441)."""
        self.pools = pools
        if iam is not None:
            self.iam = iam
        if scanner is not None:
            self.scanner = scanner
            self._handler_opts["scanner"] = scanner
        if self._handler_opts.get("tier_mgr") is None:
            # The ILM plane needs the object layer; now that it exists,
            # stand the tier manager up (journal replay included) so
            # cluster-mode boots serve restore/tier admin too.
            from ..bucket.tier import TierManager
            try:
                self._handler_opts["tier_mgr"] = TierManager(pools)
            except Exception:  # noqa: BLE001 — tiering must not block boot
                pass
        self.handlers = S3Handlers(pools, **self._handler_opts)
        if self.scanner is not None \
                and hasattr(self.scanner, "attach_config"):
            self.scanner.attach_config(self.handlers.meta,
                                       self.handlers.tier_mgr)
        if self._handler_opts.get("notify") is not None:
            # cluster boot reaches here with the object layer freshly
            # bound: config-driven notification targets come up now
            self._register_config_targets(self._handler_opts["notify"])
        self._reload_replication()

    def start(self) -> "S3Server":
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        self._stall_thread = threading.Thread(
            target=self._watch_stalls, daemon=True, name="stall-watch")
        self._stall_thread.start()
        return self

    #: Requests in flight and nothing moved for this long: a stall.
    STALL_S = 3.0

    def _marking_progress(self, chunks):
        """A streamed response's chunks, each noted as progress as it
        leaves for the socket: a 256 MiB GET that takes its client four
        seconds is no stall."""
        try:
            for chunk in chunks:
                self._last_progress = time.monotonic()
                yield chunk
        finally:
            close = getattr(chunks, "close", None)
            if close is not None:
                close()

    def _watch_stalls(self) -> None:
        """A stalled process finishes no span, so this is no span: once
        a second, where requests are in flight (streams that only the
        client ends not counted) and for STALL_S none has begun, none
        has completed, no streamed response has handed on a chunk and
        no request body has been pulled from its connection, write
        what every thread is doing to the server's log, once per
        episode; an episode ends when any of those moves or no request
        is left in flight."""
        episode = warned = False
        pulls = sum(DATA_PATH.body_pulls.values())
        while not self._stall_stop.wait(1.0):
            try:
                now = time.monotonic()
                n = sum(DATA_PATH.body_pulls.values())
                if n != pulls:      # a body moved since the last look
                    pulls, self._last_progress = n, now
                began = list(self._began.values())
                idle = (now - max(self._last_progress, *began)
                        if began else 0.0)
                if idle < self.STALL_S:
                    episode = False
                elif not episode:
                    episode = True
                    self._report_stall(len(began), idle)
            except Exception:       # a watcher that died would say
                if not warned:      # "no stall" for the process's life
                    warned = True
                    print("minio_tpu: stall watcher: a look failed "
                          "(said once):\n" + traceback.format_exc(),
                          file=sys.stderr, flush=True)

    def _report_stall(self, stuck: int, idle_s: float) -> None:
        """One episode's evidence, to the server's log (standard
        error): every thread's stack, each lane's state and seconds per
        state, the host's MemAvailable."""
        from ..ops import coalesce
        DATA_PATH.record_request_stall()
        out = sys.stderr
        avail = "unknown"
        try:
            with open("/proc/meminfo") as f:
                for line in f:
                    if line.startswith("MemAvailable:"):
                        avail = " ".join(line.split()[1:])
        except OSError:
            pass
        lines = [f"minio_tpu: request stall: {time.strftime('%H:%M:%S')} "
                 f"{stuck} in flight, nothing began, completed or moved "
                 f"a chunk for {idle_s:.1f} s; MemAvailable {avail}"]
        for dev, state, seconds in coalesce.lanes_report():
            secs = " ".join(f"{st}={v:.3f}" for st, v in seconds.items())
            lines.append(f"minio_tpu: request stall: lane {dev} in "
                         f"{state}: {secs}")
        print("\n".join(lines), file=out, flush=True)
        faulthandler.dump_traceback(file=out, all_threads=True)
        print("minio_tpu: request stall: end of stacks", file=out,
              flush=True)

    def build_ladders(self, hold_ready: bool = False) -> None:
        """Ask for the device programs' shape ladders (ops/coalesce.py)
        at every parity this deployment writes with: the sets' default
        and each storage class an operator has set.  At boot, and again
        when a storage class is set; what is built already is skipped.
        `hold_ready` (boot) keeps /minio/health/ready at 503 until what
        was asked for is built, so whoever waits for readiness before
        sending load meets no compile and no oversized step; a request
        that comes earlier is served all the same.  Deciding what to
        ask for can take seconds (a host-hashed algorithm loads, on a
        first boot builds, its native kernel to learn that its digests
        stay on the host): readiness waits for that too."""
        self.warming = self.warming or hold_ready
        self._asking = self._asking or hold_ready
        try:
            cfg = self.handlers.config_sys
            for parity in {None} | {
                    cfg.parity_for_class(sc) for sc in ("standard", "rrs")
                    if cfg.is_set("storage_class", sc)}:
                self.pools.build_ladders(parity)
        finally:
            if hold_ready:
                self._asking = False

    def shutdown(self) -> None:
        # The scanner's lifecycle belongs to the process (__main__) —
        # a service RESTART tears this server down but must keep (or
        # rebuild) the scanner; stopping it here would end background
        # healing for the life of the process.
        self._stall_stop.set()
        self._httpd.shutdown()
        self._httpd.server_close()
        # The polling trace subscription is this server's: the span
        # tracer is the process's and would stay on without it.
        ring = self.__dict__.pop("_trace_ring", None)
        if ring is not None:
            ospan.TRACER.unsubscribe(ring)
        # Flush + stop the audit drain threads (file targets flush
        # their tail; queued entries drain before the sentinel).
        for t in self.audit_targets:
            try:
                t.close(timeout=2.0)
            except Exception:  # noqa: BLE001
                pass

    def drain(self, timeout: float | None = None) -> dict:
        """Graceful drain (the cmd/signals.go handleSignals role).

        Flips readiness to draining — new S3 requests bounce with
        503 + Retry-After, /minio/health/ready goes 503 so balancers
        stop routing here — then waits for every inflight request
        (through its last response byte) up to MTPU_DRAIN_TIMEOUT.
        Afterwards the durability state quiesces: digest lanes flush,
        running heal sequences stop (their frontier trackers checkpoint
        on the way out), and MRF journals persist.  Idempotent; the
        caller still owns shutdown().
        """
        import time as _time
        if timeout is None:
            timeout = float(_os.environ.get("MTPU_DRAIN_TIMEOUT",
                                            "10") or 10)
        t0 = _time.monotonic()
        deadline = t0 + timeout
        if self.worker_plane is not None and self.worker_id is not None:
            # pool mode: flip the shared slab so any worker's /metrics
            # and admin-info show this one leaving rotation
            self.worker_plane.state.set_draining(self.worker_id)
        with self._drain_cv:
            first = not self.draining
            self.draining = True
            while self._inflight > 0:
                left = deadline - _time.monotonic()
                if left <= 0:
                    break
                self._drain_cv.wait(timeout=min(left, 0.25))
            leftover = self._inflight
        # Digest lanes: every request-owned stream closed with the
        # requests above; a bounded flush covers finalize_async tails
        # still ticking through the lane scheduler.
        try:
            from ..utils import digestlanes
            digestlanes.drain(timeout=1.0)
        except Exception:  # noqa: BLE001 — drain must not die here
            pass
        # Heal frontier: stop running sequences; heal_drive saves its
        # HealingTracker checkpoint in its finally as it unwinds.
        hs = getattr(self, "heal_state", None)
        if hs is not None:
            for s in list(getattr(hs, "_seqs", {}).values()):
                try:
                    s.stop()
                except Exception:  # noqa: BLE001
                    pass
        # Replication: compact the intent journal so the next boot
        # replays a checkpoint instead of the whole tail.  NOT stop()
        # — a service RESTART reuses this pool and its workers.
        rp = getattr(self.handlers, "replication", None)
        if rp is not None:
            try:
                rp.checkpoint()
            except Exception:  # noqa: BLE001
                pass
        # MRF: persist pending heals so the next boot replays them.
        seen: set[int] = set()
        if self.pools is not None:
            for pool in getattr(self.pools, "pools", [self.pools]):
                for es in getattr(pool, "sets", [pool]):
                    q = getattr(es, "mrf", None)
                    if q is not None and id(q) not in seen:
                        seen.add(id(q))
                        cp = getattr(q, "checkpoint", None)
                        if cp is not None:
                            try:
                                cp()
                            except Exception:  # noqa: BLE001
                                pass
        dur = _time.monotonic() - t0
        if first:
            from ..observe.metrics import DATA_PATH
            DATA_PATH.record_drain(leftover, dur)
            self.log.info(
                f"drain complete: {leftover} request(s) leftover "
                f"after {dur:.2f}s")
        return {"draining": True, "leftover": leftover,
                "duration_s": dur}

    @property
    def endpoint(self) -> str:
        scheme = "https" if self.tls else "http"
        return f"{scheme}://{self.host}:{self.port}"

    # -- auth + dispatch -----------------------------------------------------

    def _read_body(self, req) -> bytes:
        length = int(req.headers.get("Content-Length", 0) or 0)
        if length > MAX_HEADER_BODY:
            raise S3Error("EntityTooLarge")
        if length:
            with ospan.span("http.read_body"):
                return req.rfile.read(length)
        if req.headers.get("Transfer-Encoding", "").lower() == "chunked":
            # HTTP chunked framing (not aws-chunked).
            out = bytearray()
            while True:
                line = req.rfile.readline().strip()
                size = int(line.split(b";")[0], 16)
                if size == 0:
                    req.rfile.readline()
                    break
                out += req.rfile.read(size)
                req.rfile.readline()
            return bytes(out)
        return b""

    def _lookup_creds(self, access_key: str) -> Credentials | None:
        """Root first, then IAM identities (users/service/STS)."""
        if access_key == self.creds.access_key:
            return self.creds
        if self.iam is not None:
            ident = self.iam.lookup(access_key)
            if ident is not None:
                return Credentials(ident.access_key, ident.secret_key,
                                   self.creds.region)
        return None

    def _authenticate(self, req, path: str,
                      query: dict) -> tuple[bytes, str]:
        """Classify + verify auth; returns (decoded body, access_key).
        cf. checkRequestAuthType, cmd/auth-handler.go:281."""
        headers = {k: v for k, v in req.headers.items()}
        headers.setdefault("Host", f"{self.host}:{self.port}")
        body = self._read_body(req)
        if "X-Amz-Signature" in query:
            ak = verify_presigned(self._lookup_creds, req.command, path,
                                  query, headers)
            self._check_session_token(
                ak, query.get("X-Amz-Security-Token", [""])[0])
            return body, ak
        from . import sigv2
        if sigv2.is_v2_presigned(query):
            ak = sigv2.verify_presigned_v2(self._lookup_creds,
                                           req.command, path, query,
                                           headers)
            self._check_session_token(
                ak, query.get("X-Amz-Security-Token",
                              query.get("SecurityToken", [""]))[0]
                or req.headers.get("x-amz-security-token", ""))
            return body, ak
        auth = req.headers.get("Authorization", "")
        if not auth:
            # Anonymous: allowed only where the bucket policy grants it
            # (the PolicySys role, cmd/bucket-policy.go) — _authorize
            # makes that call with access_key "".
            return body, ""
        if sigv2.is_v2_header(auth):
            ak = sigv2.verify_header_v2(self._lookup_creds, req.command,
                                        path, query, headers)
            self._check_session_token(
                ak, req.headers.get("x-amz-security-token", ""))
            return body, ak
        payload_decl, ak = verify_header_signature(
            self._lookup_creds, req.command, path, query, headers, body)
        self._check_session_token(
            ak, req.headers.get("x-amz-security-token", ""))
        if payload_decl == STREAMING_PAYLOAD:
            body = decode_streaming_body(self._lookup_creds, headers, body)
        return body, ak

    def _body_reader(self, req):
        """The raw request body as a bounded reader (no buffering)."""
        length = int(req.headers.get("Content-Length", 0) or 0)
        if length > MAX_HEADER_BODY:
            raise S3Error("EntityTooLarge")
        if req.headers.get("Transfer-Encoding", "").lower() == "chunked":
            # No declared length: bound the stream so chunked TE can't
            # bypass the 5 GiB part limit.
            return streams.MaxSizeReader(
                streams.HTTPChunkedReader(req.rfile), MAX_HEADER_BODY,
                exc=lambda msg: S3Error("EntityTooLarge"))
        # Plain TCP + a declared length: the body's pulls are one native
        # call each.  An SSLSocket's record layer is in Python's hands
        # (the same branch as _respond's zero-copy writer), and without
        # the library rfile serves.
        sock = req.connection
        if (self._recv_exact is not None
                and not isinstance(sock, _ssl.SSLSocket)):
            return streams.SocketBodyReader(req.rfile, length, sock,
                                            self._recv_exact)
        return streams.LimitedReader(req.rfile, length)

    def _authenticate_streaming(self, req, path: str, query: dict):
        """Auth for stream-eligible requests: verify the signature from
        headers alone and return (body reader, access_key) — the body
        never lands in server memory whole.  Signed-payload requests get
        a SHA-256-verifying reader (hash checked at EOF, like the
        reference's hash.Reader); aws-chunked bodies a per-chunk
        signature-verifying decoder."""
        headers = {k: v for k, v in req.headers.items()}
        headers.setdefault("Host", f"{self.host}:{self.port}")
        raw = self._body_reader(req)
        if "X-Amz-Signature" in query:
            ak = verify_presigned(self._lookup_creds, req.command, path,
                                  query, headers)
            self._check_session_token(
                ak, query.get("X-Amz-Security-Token", [""])[0])
            return raw, ak
        from . import sigv2
        if sigv2.is_v2_presigned(query):
            ak = sigv2.verify_presigned_v2(self._lookup_creds,
                                           req.command, path, query,
                                           headers)
            self._check_session_token(
                ak, query.get("X-Amz-Security-Token",
                              query.get("SecurityToken", [""]))[0]
                or req.headers.get("x-amz-security-token", ""))
            return raw, ak
        auth = req.headers.get("Authorization", "")
        if not auth:
            return raw, ""
        if sigv2.is_v2_header(auth):
            # V2 signs no payload hash; the body streams unverified
            # (exactly the reference's V2 semantics).
            ak = sigv2.verify_header_v2(self._lookup_creds, req.command,
                                        path, query, headers)
            self._check_session_token(
                ak, req.headers.get("x-amz-security-token", ""))
            return raw, ak
        payload_decl, ak = verify_header_signature(
            self._lookup_creds, req.command, path, query, headers,
            body=None)
        self._check_session_token(
            ak, req.headers.get("x-amz-security-token", ""))
        if payload_decl == STREAMING_PAYLOAD:
            decoded = StreamingSigV4Reader(self._lookup_creds, headers,
                                           raw)
            declared = int(req.headers.get("x-amz-decoded-content-length",
                                           0) or 0)
            if declared:
                # The declared decoded length feeds quota/size admission
                # (handlers.put_object); hold the stream to it.
                decoded = streams.ExactLengthReader(
                    decoded, declared,
                    exc=lambda msg: S3Error("IncompleteBody", msg))
            return decoded, ak
        if payload_decl != UNSIGNED_PAYLOAD:
            raw = streams.HashVerifyReader(
                raw, payload_decl,
                exc=lambda msg: S3Error("XAmzContentSHA256Mismatch"))
        return raw, ak

    @staticmethod
    def _stream_eligible(method: str, path: str, query: dict) -> bool:
        """Data PUTs (object body / multipart part) stream; small-body
        subresource PUTs and everything else buffer as before."""
        if method != "PUT":
            return False
        parts = path.lstrip("/").split("/", 1)
        if len(parts) < 2 or not parts[1]:
            return False                 # bucket-level PUT (config XML)
        return not any(q in query for q in
                       ("tagging", "retention", "legal-hold"))

    def _check_session_token(self, access_key: str, token: str) -> None:
        """STS credentials must present their session token."""
        if self.iam is None:
            return
        ident = self.iam.lookup(access_key)
        if ident is not None and ident.kind == "sts":
            if token != ident.session_token:
                raise S3Error("InvalidAccessKeyId",
                              "missing or wrong session token")

    # -- authorization (cf. checkRequestAuthType policy check) ---------------

    _CONFIG_ACTIONS = {
        "lifecycle": "LifecycleConfiguration",
        "policy": "BucketPolicy",
        "notification": "BucketNotification",
        "replication": "ReplicationConfiguration",
        "quota": "BucketPolicy",
        "object-lock": "BucketObjectLockConfiguration",
        "tagging": "BucketTagging",
        "encryption": "EncryptionConfiguration",
    }

    @staticmethod
    def _s3_action(method: str, bucket: str, key: str, query: dict) -> str:
        verb = {"GET": "Get", "HEAD": "Get", "PUT": "Put",
                "DELETE": "Delete"}.get(method, "Get")
        if key:
            for sub, base in (("tagging", "ObjectTagging"),
                              ("retention", "ObjectRetention"),
                              ("legal-hold", "ObjectLegalHold")):
                if sub in query:
                    return f"s3:{verb}{base}"
        elif bucket:
            for sub, base in S3Server._CONFIG_ACTIONS.items():
                if sub in query:
                    return f"s3:{verb}{base}"
        if not bucket:
            return "s3:ListAllMyBuckets"
        if not key:
            if method == "GET":
                if "location" in query:
                    return "s3:GetBucketLocation"
                if "versioning" in query:
                    return "s3:GetBucketVersioning"
                if "uploads" in query:
                    return "s3:ListBucketMultipartUploads"
                return "s3:ListBucket"
            if method == "HEAD":
                return "s3:ListBucket"
            if method == "PUT":
                if "versioning" in query:
                    return "s3:PutBucketVersioning"
                return "s3:CreateBucket"
            if method == "DELETE":
                return "s3:DeleteBucket"
            if method == "POST" and "delete" in query:
                return "s3:DeleteObject"
            return "s3:ListBucket"
        if method in ("GET", "HEAD"):
            if "uploadId" in query:
                return "s3:ListMultipartUploadParts"
            return ("s3:GetObjectVersion" if "versionId" in query
                    else "s3:GetObject")
        if method == "PUT":
            return "s3:PutObject"
        if method == "DELETE":
            if "uploadId" in query:
                return "s3:AbortMultipartUpload"
            return ("s3:DeleteObjectVersion" if "versionId" in query
                    else "s3:DeleteObject")
        if method == "POST":
            if "select" in query:
                return "s3:GetObject"
            if "restore" in query:
                return "s3:RestoreObject"
            return "s3:PutObject"
        return "s3:GetObject"

    def _authorize(self, access_key: str, method: str, bucket: str,
                   key: str, query: dict, source_ip: str = "") -> None:
        action = self._s3_action(method, bucket, key, query)
        resource = f"{bucket}/{key}" if key else bucket
        ctx = {"s3:prefix": query.get("prefix", [""])[0],
               "aws:SourceIp": source_ip}
        if access_key == "":
            # Anonymous request: only a bucket policy can grant it
            # (cf. PolicySys.IsAllowed for anonymous,
            # cmd/auth-handler.go + cmd/bucket-policy.go).
            if bucket:
                data = self.handlers.meta.get(bucket, "policy")
                if data is not None:
                    from ..iam.policy import Policy, PolicyError
                    try:
                        if Policy(data.decode()).is_allowed(
                                action, resource, ctx, principal="*"):
                            return
                    except (PolicyError, ValueError):
                        pass
            raise S3Error("AccessDenied", "anonymous access denied")
        if access_key == self.creds.access_key or self.iam is None:
            return                               # root bypasses policy
        ident = self.iam.lookup(access_key)
        if ident is None:
            raise S3Error("InvalidAccessKeyId")
        if not self.iam.is_allowed(ident, action, resource, ctx):
            raise S3Error("AccessDenied",
                          f"{action} on {resource} denied")

    # -- admin API (cf. registerAdminRouter, cmd/admin-router.go:40) ---------

    # Endpoint -> madmin-style admin policy action (cf. AdminAction
    # constants, github.com/minio/pkg/iam/policy/admin-action.go).
    _ADMIN_ACTIONS = {
        "info": "admin:ServerInfo",
        "datausage": "admin:DataUsageInfo",
        "heal": "admin:Heal",
        "trace": "admin:ServerTrace",
        "console": "admin:ConsoleLog",
        "users": "admin:*User",          # method-refined below
        "bucket-remote": "admin:SetBucketTarget",
        "service-accounts": "admin:*ServiceAccount",
        "groups": "admin:*Group",
        "policies": "admin:*Policy",
        "config": "admin:ConfigUpdate",
        "config-help": "admin:ConfigUpdate",
        "profile": "admin:Profiling",
        "service": "admin:ServiceRestart",
        "tier": "admin:SetTier",
        "ilm": "admin:SetTier",
        # replication diagnostics + resync trigger (cf.
        # ReplicationDiag / SetBucketTarget admin actions)
        "replication": "admin:SetBucketTarget",
        "inspect": "admin:InspectData",
        "kms": "admin:KMSKeyStatus",
        "top": "admin:ServerTrace",
        "listen": "admin:ListenNotification",
        "bandwidth": "admin:BandwidthMonitor",
        "pools": "admin:ServerInfo",
        # pool lifecycle: add + decommission are WRITE actions (cf.
        # DecommissionAdminAction, madmin-go); GET status refines to
        # ServerInfo below.
        "pool": "admin:Decommission",
        "site-replication": "admin:SiteReplicationInfo",
        # Fleet observability (cf. PrometheusAdminAction /
        # HealthInfoAdminAction, madmin-go).
        "metrics": "admin:Prometheus",
        "healthinfo": "admin:OBDInfo",
    }

    def _admin_authorize(self, access_key: str, sub: str,
                         method: str) -> None:
        """Root always; otherwise an IAM identity whose policies allow
        the endpoint's admin: action (cf. checkAdminRequestAuth,
        cmd/admin-handler-utils.go — non-root admins are first-class)."""
        if access_key == self.creds.access_key:
            return
        if self.iam is None or not access_key:
            raise S3Error("AccessDenied", "admin API requires credentials")
        ident = self.iam.lookup(access_key)
        if ident is None:
            raise S3Error("InvalidAccessKeyId")
        base = self._ADMIN_ACTIONS.get(sub.split("/")[0], "admin:*")
        if base == "admin:KMSKeyStatus" and method == "POST":
            # Key creation is a WRITE action — a status-only admin
            # must not mint keys (cf. KMSCreateKeyAdminAction).
            base = "admin:KMSCreateKey"
        if base == "admin:*User":
            base = {"GET": "admin:ListUsers", "POST": "admin:CreateUser",
                    "DELETE": "admin:DeleteUser"}.get(method,
                                                      "admin:CreateUser")
        elif base == "admin:*Group":
            base = {"GET": "admin:ListGroups",
                    "POST": "admin:AddUserToGroup",
                    "DELETE": "admin:RemoveUserFromGroup"}.get(
                method, "admin:AddUserToGroup")
        elif base == "admin:*Policy":
            base = {"GET": "admin:GetPolicy", "POST": "admin:CreatePolicy",
                    "DELETE": "admin:DeletePolicy"}.get(
                method, "admin:CreatePolicy")
        elif base == "admin:*ServiceAccount":
            base = {"GET": "admin:ListServiceAccounts",
                    "POST": "admin:CreateServiceAccount",
                    "DELETE": "admin:RemoveServiceAccount"}.get(
                method, "admin:CreateServiceAccount")
        elif base == "admin:Decommission" and method == "GET":
            base = "admin:ServerInfo"        # status is read-only
        elif base == "admin:SiteReplicationInfo" and method != "GET":
            # membership mutations are WRITE actions (cf.
            # SiteReplicationAddAction / SiteReplicationRemoveAction)
            base = "admin:SiteReplicationOperation"
        if not self.iam.is_allowed(ident, base, "*"):
            raise S3Error("AccessDenied", f"{base} denied")

    def _register_config_targets(self, notify) -> None:
        """Boot-time notification wiring: (1) build + register every
        enabled notify_* config target (internal/config/notify role);
        (2) RELOAD persisted bucket notification rules — they live in
        each bucket's metadata, and a fresh NotificationSystem that
        never loads them would silently drop events after every
        restart until each bucket's config is re-PUT."""
        try:
            from ..bucket.event_targets import targets_from_config
            import os as _os
            store = _os.environ.get("MTPU_NOTIFY_STORE_DIR") or None
            for t in targets_from_config(self.handlers.config_sys,
                                         store_dir=store):
                notify.register_target(t)
        except Exception as e:  # noqa: BLE001 — notification targets
            self.log.error(f"notify config targets: {e}")   # are not
                                                            # boot-fatal
        try:
            from ..bucket.notify import parse_notification_config
            for bucket in self.pools.list_buckets():
                if bucket.startswith(".mtpu"):
                    continue
                raw = self.handlers.meta.get(bucket, "notification")
                if raw:
                    notify.set_bucket_rules(
                        bucket, parse_notification_config(raw))
        except Exception as e:  # noqa: BLE001
            self.log.error(f"notify rule reload: {e}")

    def _may_replicate(self, access_key: str) -> bool:
        """s3:ReplicateObject gate for the incoming REPLICA marker."""
        if access_key == self.creds.access_key:
            return True                      # root (registered targets
        if self.iam is None or not access_key:   # usually use root)
            return False
        ident = self.iam.lookup(access_key)
        return ident is not None and self.iam.is_allowed(
            ident, "s3:ReplicateObject", "*")

    def _wire_replication(self, bucket: str) -> None:
        """(Re)wire one bucket's replication rules + remote targets
        into the worker pool (no-op until both halves exist)."""
        pool = self.handlers.replication if self.handlers else None
        if pool is None:
            return
        try:
            from ..bucket.replication import wire_bucket
            wire_bucket(pool, self.handlers.meta, bucket)
        except Exception as e:  # noqa: BLE001 — replication wiring is
            self.log.error(f"replication wiring {bucket}: {e}")  # async

    def _reload_replication(self) -> None:
        """Boot: every bucket with a persisted replication config +
        registered targets starts replicating again (restart must not
        silently stop replication, same rule as notification rules)."""
        if self.handlers is None or self.handlers.replication is None \
                or self.pools is None:
            return
        try:
            for bucket in self.pools.list_buckets():
                if not bucket.startswith(".mtpu"):
                    self._wire_replication(bucket)
        except Exception as e:  # noqa: BLE001
            self.log.error(f"replication reload: {e}")

    def _site_sys(self):
        """Lazy SiteReplicationSys bound to this server's stack."""
        if getattr(self, "_site_sys_obj", None) is None:
            from ..cluster.site_replication import SiteReplicationSys
            self._site_sys_obj = SiteReplicationSys(
                self.pools, self.iam, self.handlers.meta,
                creds=self.creds)
        return self._site_sys_obj

    def _site_hook(self, what: str, bucket: str = "") -> None:
        """After a local IAM/bucket mutation: if this server is in a
        site group, fan the change out ASYNCHRONOUSLY, single-flight —
        a mutation must not block on (or cascade through) the whole
        group; peers' pushes carry srInternal and never re-enter this
        hook. Bucket DELETES additionally push explicit DeleteBucket
        to every peer (reconcile is deliberately additive — a sweep
        that deleted "extra" remote buckets could destroy data a peer
        created while we were partitioned). Best-effort: reconcile
        repairs anything missed."""
        try:
            sys_ = self._site_sys()    # loads persisted state: a hook
        except Exception:  # noqa: BLE001    # must fire after restarts
            return
        if not sys_.enabled:
            return
        if what == "bucket-delete" and bucket:
            import threading as _thr

            def drop():
                for peer in sys_._peers():
                    try:
                        peer.delete_bucket(bucket)
                    except Exception:  # noqa: BLE001
                        pass
            _thr.Thread(target=drop, daemon=True,
                        name="site-repl-bucket-del").start()
        with self._site_hook_mu:
            if self._site_hook_busy:
                self._site_hook_again = True
                return
            self._site_hook_busy = True
            self._site_hook_again = False

        def run():
            while True:
                try:
                    sys_.reconcile()
                except Exception:  # noqa: BLE001
                    pass
                # exit-decision and busy-clear are ATOMIC: a mutation
                # landing after the check would otherwise set again=True
                # on a worker that already chose to exit (lost wakeup)
                with self._site_hook_mu:
                    if not self._site_hook_again:
                        self._site_hook_busy = False
                        return
                    self._site_hook_again = False
        threading.Thread(target=run, daemon=True,
                         name="site-repl-hook").start()

    def _pool_self_test(self, es) -> None:
        """Probe every lane of a candidate pool BEFORE it becomes
        placement-eligible: one put/get/delete round-trip per erasure
        set.  A pool with a dead drive path must fail the admin call,
        not the first client write routed onto it."""
        probe_bucket = ".mtpu.pool-selftest"
        try:
            es.make_bucket(probe_bucket)
        except StorageError:
            pass
        try:
            for i, s in enumerate(es.sets):
                payload = secrets.token_bytes(1024)
                key = f"probe-{i}"
                s.put_object(probe_bucket, key, payload)
                _, got = s.get_object(probe_bucket, key)
                if bytes(got) != payload:
                    raise ValueError(
                        f"pool self-test: set {i} read mismatch")
                s.delete_object(probe_bucket, key)
        finally:
            try:
                es.delete_bucket(probe_bucket, force=True)
            except StorageError:
                pass

    def _pool_add(self, spec: str,
                  set_drive_count: int | None = None) -> int:
        """Attach a new pool live: expand the drive spec, format +
        recovery-sweep + health-wrap (the boot stack), self-test its
        lanes, replicate the bucket set, attach an MRF queue, then
        propagate the topology to sibling workers."""
        from .__main__ import expand_ellipses
        from .topology import build_pool
        paths = []
        for part in spec.split():
            paths.extend(expand_ellipses(part))
        if not paths:
            raise ValueError("empty drives spec")
        es = build_pool(paths, set_drive_count,
                        self.pools.deployment_id, sweep=True)
        self._pool_self_test(es)
        idx = self.pools.add_pool(es)
        from ..background.mrf import attach_mrf
        attach_mrf(es)
        self._propagate_topology()
        return idx

    def _propagate_topology(self) -> None:
        """Persist pool-topology.json and wake sibling workers (shared
        topology generation) — no-op extras in single-process mode."""
        from .topology import save_topology
        save_topology(self.pools)
        if self.worker_plane is not None:
            self.worker_plane.state.bump_topology_gen()

    def _dispatch_admin(self, access_key: str, method: str, path: str,
                        query: dict, body: bytes) -> Response:
        import json as _json
        import time as _time
        sub = path[len("/minio/admin/v1/"):].strip("/")
        self._admin_authorize(access_key, sub, method)
        j = lambda obj, status=200: Response(
            status, _json.dumps(obj).encode(),
            {"Content-Type": "application/json"})

        if sub == "info" and method == "GET":
            # madmin.InfoMessage shape (cf. ServerInfoHandler,
            # cmd/admin-handlers.go + madmin-go InfoMessage).
            from ..observe.health import cluster_health
            ok, detail = cluster_health(self.pools)
            n_buckets = len([b for b in self.pools.list_buckets()
                             if b != ".mtpu.sys"])
            n_objects = usage_size = 0
            if self.scanner is not None:
                u = self.scanner.latest_usage()
                if u is not None:
                    for b, bu in u.buckets.items():
                        n_objects += bu.objects
                        usage_size += bu.bytes
            drives = []
            for pi, pool in enumerate(self.pools.pools):
                for si, s in enumerate(pool.sets):
                    for di, d in enumerate(s.drives):
                        if d is None:
                            state = "offline"
                        elif hasattr(d, "health_state"):
                            # HealthWrappedDrive: live breaker state
                            # (ok / suspect / offline-circuit-open).
                            state = d.health_state()
                        elif hasattr(d, "is_online") and not d.is_online():
                            state = "offline"
                        else:
                            state = "ok"
                        row = {
                            "pool_index": pi, "set_index": si,
                            "drive_index": di,
                            "state": state,
                            "endpoint": getattr(d, "root", ""),
                        }
                        if hasattr(d, "health_info"):
                            hi = d.health_info()
                            row["breaker"] = {
                                "consecutive_errors":
                                    hi.get("consecutive_errors", 0),
                                "consecutive_slow":
                                    hi.get("consecutive_slow", 0),
                                "last_fault": hi.get("last_fault", ""),
                                "transitions": hi.get("transitions", []),
                            }
                        drives.append(row)
            # Per-peer liveness (cluster deployments): online/offline,
            # flap count, last-answer staleness, adaptive RPC deadline
            # — the madmin per-server state rows' analogue.
            peers = (self.cluster_node.peer_info()
                     if self.cluster_node is not None else [])
            # Pre-fork pool view (server/workers.py): per-worker
            # liveness/respawn rows + the owner/arena/ring plane.
            pool_proc = (self.worker_plane.workers_info()
                         if self.worker_plane is not None else None)
            # Device lane plane (PR 10): one row per coalescer lane —
            # which erasure sets are affine to it, how deep its queue
            # is, and how much it has dispatched.
            from ..ops import coalesce as _co
            from ..ops import devices as _devices
            lane_stats = {}
            try:
                lane_stats = _co.get().lane_stats()
            except Exception:  # noqa: BLE001 — lanes are best-effort
                pass
            dev_sets: dict[int, list[str]] = {}
            for pi, pool in enumerate(self.pools.pools):
                if hasattr(pool, "device_map"):
                    for dev, idxs in pool.device_map().items():
                        dev_sets.setdefault(dev, []).extend(
                            f"p{pi}s{i}" for i in idxs)
            device_rows = []
            for dev in range(_devices.n_devices()):
                ls = lane_stats.get(dev, {})
                device_rows.append({
                    "device": dev,
                    "lane_depth": ls.get("pending_items", 0),
                    "dispatches": ls.get("dispatches", 0),
                    "items": ls.get("items", 0),
                    "occupancy": ls.get("occupancy", 0.0),
                    "sets": dev_sets.get(dev, []),
                })
            return j({
                "mode": "online" if ok else "degraded",
                "peers": peers,
                "pool": pool_proc,
                "devices": device_rows,
                "deploymentID": self.pools.deployment_id,
                "buckets": {"count": n_buckets},
                "objects": {"count": n_objects},
                "usage": {"size": usage_size},
                "servers": [{
                    "state": "online",
                    "endpoint": f"{self.host}:{self.port}",
                    "drives": drives,
                }],
                "backend": {"backendType": "Erasure",
                            "sets": detail["sets"]},
                # back-compat keys (round-2 admin clients/tests)
                "deploymentId": self.pools.deployment_id,
                "sets": detail["sets"],
            })
        if sub == "metrics/cluster" and method == "GET":
            # Fleet scrape (cmd/metrics-v2.go cluster collection over
            # peer REST clients): render locally, fan the metrics_text
            # verb to every peer under the deadline budget, and merge
            # into one exposition where every sample carries a `node`
            # label.  mtpu_node_up marks which peers answered — a dead
            # peer is 0, never a hung scrape.
            from ..observe.metrics import merge_prom
            results, node_up = self._obs_fanout("metrics_text")
            text = merge_prom(sorted(results.items()))
            up = ["# HELP mtpu_node_up Node answered the cluster "
                  "scrape within the deadline budget",
                  "# TYPE mtpu_node_up gauge"]
            up += [f'mtpu_node_up{{node="{n}"}} {v}'
                   for n, v in sorted(node_up.items())]
            text += "\n".join(up) + "\n"
            return Response(200, text.encode(),
                            {"Content-Type":
                             "text/plain; version=0.0.4"})
        if sub == "healthinfo" and method == "GET":
            # Fleet health document (cmd/admin-handlers.go HealthInfo):
            # same peer fan-out, JSON merge keyed by node endpoint.
            results, node_up = self._obs_fanout("healthinfo")
            return j({"nodes": results, "node_up": node_up})
        if sub == "datausage" and method == "GET":
            if self.scanner is None:
                return j({"error": "scanner not running"}, 503)
            usage = self.scanner.latest_usage()
            if usage is None:
                usage = self.scanner.scan_cycle()
            return j({"buckets": {b: u.to_obj()
                                  for b, u in usage.buckets.items()},
                      "scannedAt": usage.scanned_at})
        if sub == "heal":
            if not hasattr(self, "heal_state"):
                from ..background.heal_ops import HealState
                self.heal_state = HealState(self.pools)
            if method == "POST":
                seq = self.heal_state.launch(
                    bucket=query.get("bucket", [""])[0],
                    prefix=query.get("prefix", [""])[0],
                    deep=query.get("deep", [""])[0] == "true")
                return j(seq.status())
            return j({"sequences": self.heal_state.statuses()})
        if sub == "trace" and method == "GET" \
                and query.get("trees", [""])[0] == "1":
            # The retention ring's whole records (MTPU_TRACE_RING),
            # oldest first, request roots and `lane.dispatch` roots
            # alike; reads, does not drain, and leaves the flat poll's
            # queue alone.
            return j({"traces": ospan.TRACER.traces()})
        if sub == "trace" and method == "GET":
            # Polling form of the one trace plane: the first call
            # subscribes (which turns span tracing on); each call
            # drains what completed since, one flat line per request.
            if not hasattr(self, "_trace_ring"):
                self._trace_ring = ospan.TRACER.subscribe(2000)
            q = self._trace_ring
            recs = [q.popleft() for _ in range(len(q))]
            return j({"trace": [f for f in map(ospan.flat, recs)
                                if f is not None]})
        if sub == "trace" and method == "POST":
            # Live span-trace stream (cf. TraceHandler,
            # cmd/admin-handlers.go): chunked NDJSON of completed
            # request span trees off the span PubSub, server-side
            # filtered. `duration` (seconds) bounds the stream for
            # polling clients; without it the stream runs until the
            # client hangs up.
            from ..observe.span import TRACER, TraceFilter
            flat = {k: v[0] if v else "" for k, v in query.items()}
            filt = TraceFilter.from_query(flat)
            try:
                max_s = float(flat.get("duration", 0) or 0)
            except ValueError:
                max_s = 0.0
            return Response(
                200, b"",
                {"Content-Type": "application/x-ndjson",
                 "Transfer-Encoding": "chunked"},
                body_iter=self._span_stream(TRACER, filt, max_s))
        if sub == "top/apis" and method == "GET":
            from ..observe.span import TRACER
            return j(TRACER.snapshot())
        if sub == "console" and method == "GET":
            n = int(query.get("n", ["100"])[0] or 100)
            return j({"log": self.log_ring.tail(n)})
        if sub == "users":
            if self.iam is None:
                return j({"error": "IAM not enabled"}, 501)
            if method == "GET":
                return j({"users": self.iam.list_users()})
            if method == "POST":
                req_obj = _json.loads(body or b"{}")
                try:
                    if req_obj.get("attachPolicies") is not None:
                        # policy-mapping update for an EXISTING identity
                        # (cf. SetPolicyForUserOrGroup)
                        self.iam.attach_policy(
                            req_obj["accessKey"],
                            req_obj["attachPolicies"])
                    else:
                        self.iam.add_user(req_obj["accessKey"],
                                          req_obj["secretKey"],
                                          req_obj.get("policies", []),
                                          status=req_obj.get(
                                              "status", "enabled"))
                except (KeyError, ValueError) as e:
                    raise S3Error("InvalidArgument", str(e)) from None
                if not req_obj.get("srInternal"):
                    self._site_hook("iam")
                return j({"ok": True})
            if method == "DELETE":
                self.iam.remove_user(query.get("accessKey", [""])[0])
                if not query.get("srInternal"):
                    self._site_hook("iam")
                return j({"ok": True})
        if sub == "service-accounts":
            # cf. AddServiceAccount / ListServiceAccounts,
            # cmd/admin-handlers-users.go; explicit credentials are the
            # site-replication import path.
            if self.iam is None:
                return j({"error": "IAM not enabled"}, 501)
            if method == "GET":
                return j({"accounts": self.iam.list_service_accounts(
                    query.get("parent", [""])[0])})
            if method == "POST":
                req_obj = _json.loads(body or b"{}")
                try:
                    ident = self.iam.add_service_account(
                        req_obj["parent"],
                        req_obj.get("policies", []),
                        access_key=req_obj.get("accessKey", ""),
                        secret_key=req_obj.get("secretKey", ""))
                except KeyError as e:
                    raise S3Error("InvalidArgument", str(e)) from None
                if not req_obj.get("srInternal"):
                    self._site_hook("iam")
                return j({"accessKey": ident.access_key,
                          "secretKey": ident.secret_key})
            if method == "DELETE":
                self.iam.remove_user(query.get("accessKey", [""])[0])
                self._site_hook("iam")
                return j({"ok": True})
        if sub == "policies":
            if self.iam is None:
                return j({"error": "IAM not enabled"}, 501)
            if method == "GET":
                name = query.get("name", [""])[0]
                if name:
                    try:
                        return j({"name": name,
                                  "policy": self.iam.get_policy_doc(name)})
                    except KeyError:
                        return j({"error": f"no policy {name!r}"}, 404)
                return j({"policies": self.iam.list_policies()})
            if method == "POST":
                req_obj = _json.loads(body or b"{}")
                try:
                    self.iam.set_policy(req_obj["name"],
                                        req_obj["policy"])
                except (KeyError, ValueError) as e:
                    raise S3Error("InvalidArgument", str(e)) from None
                if not req_obj.get("srInternal"):
                    self._site_hook("iam")
                return j({"ok": True})
            if method == "DELETE":
                try:
                    self.iam.remove_policy(query.get("name", [""])[0])
                except KeyError as e:
                    return j({"error": f"no policy {e}"}, 404)
                except ValueError as e:     # built-in policy
                    return j({"error": str(e)}, 409)
                if not query.get("srInternal"):
                    self._site_hook("iam")
                return j({"ok": True})
        if sub == "groups":
            # Group CRUD + policy attach (cf. cmd/admin-handlers-users.go
            # UpdateGroupMembers/SetPolicyForUserOrGroup).
            if self.iam is None:
                return j({"error": "IAM not enabled"}, 501)
            if method == "GET":
                name = query.get("name", [""])[0]
                if name:
                    try:
                        return j(self.iam.group_info(name))
                    except KeyError:
                        return j({"error": f"no group {name!r}"}, 404)
                return j({"groups": self.iam.list_groups()})
            if method == "POST":
                req_obj = _json.loads(body or b"{}")
                try:
                    name = req_obj["name"]
                    if req_obj.get("removeMembers"):
                        self.iam.remove_group_members(
                            name, req_obj["removeMembers"])
                    else:
                        self.iam.add_group(name,
                                           req_obj.get("members", []),
                                           req_obj.get("policies"))
                    if "setPolicies" in req_obj:
                        self.iam.set_group_policy(name,
                                                  req_obj["setPolicies"])
                except KeyError as e:
                    raise S3Error("InvalidArgument", str(e)) from None
                if not req_obj.get("srInternal"):
                    self._site_hook("iam")
                return j({"ok": True})
            if method == "DELETE":
                try:
                    self.iam.remove_group(query.get("name", [""])[0])
                except KeyError as e:
                    return j({"error": f"no group {e}"}, 404)
                except ValueError as e:
                    return j({"error": str(e)}, 409)
                if not query.get("srInternal"):
                    self._site_hook("iam")
                return j({"ok": True})
        if sub == "config":
            if not hasattr(self, "config") or self.config is None:
                # Shared with the data path: the PUT handler reads
                # storage_class parity from the same instance, so an
                # admin `config set` applies without a restart.
                self.config = self.handlers.config_sys
            if method == "GET":
                subsys = query.get("subsys", [""])[0]
                if subsys:
                    return j({subsys: self.config.get_subsys(subsys)})
                return j(self.config.help())
            if method == "POST":
                req_obj = _json.loads(body or b"{}")
                try:
                    self.config.set(req_obj["subsys"], req_obj["key"],
                                    req_obj["value"])
                except KeyError as e:
                    raise S3Error("InvalidArgument", str(e)) from None
                if req_obj["subsys"] == "storage_class":
                    self.build_ladders()
                return j({"ok": True})
        if sub == "config-help" and method == "GET":
            if not hasattr(self, "config") or self.config is None:
                self.config = self.handlers.config_sys
            return j(self.config.help(query.get("subsys", [""])[0]))
        if sub == "profile":
            # cf. StartProfilingHandler/DownloadProfilingHandler,
            # cmd/admin-handlers.go:491,599 — cProfile in place of
            # pprof. In a cluster the start FANS OUT to every peer and
            # the download collects all nodes' profiles into one zip,
            # like the reference's profiling archive.
            import cProfile
            import io as _io
            import pstats
            peers = getattr(self, "peer_notification", None)
            if method == "POST":
                started = 0
                if getattr(self, "_profiler", None) is None:
                    self._profiler = cProfile.Profile()
                    self._profiler.enable()
                    started = 1
                peer_started = 0
                if peers is not None:
                    res = peers._fan_out("peer.profile_start", {})
                    peer_started = sum(1 for r, e in res
                                       if e is None and r)
                if started or peer_started:
                    return j({"profiling": "started",
                              "nodes": started + peer_started})
                return j({"profiling": "already running"}, 409)
            if method == "GET":
                prof = getattr(self, "_profiler", None)
                if prof is None:
                    return j({"error": "profiling not running"}, 404)
                prof.disable()
                self._profiler = None
                buf = _io.StringIO()
                pstats.Stats(prof, stream=buf).sort_stats(
                    "cumulative").print_stats(50)
                local_text = buf.getvalue()
                want_zip = (query.get("format", [""])[0] == "zip"
                            or peers is not None)
                if not want_zip:
                    return Response(200, local_text.encode(),
                                    {"Content-Type": "text/plain"})
                import zipfile
                blob = _io.BytesIO()
                with zipfile.ZipFile(blob, "w",
                                     zipfile.ZIP_DEFLATED) as z:
                    z.writestr("profile-local.txt", local_text)
                    if peers is not None:
                        for cli, (r, e) in zip(
                                peers.peers,
                                peers._fan_out("peer.profile_dump",
                                               {})):
                            name = (f"profile-{cli.host}-"
                                    f"{cli.port}.txt")
                            if e is not None:
                                z.writestr(name + ".error", str(e))
                            elif r and r.get("text"):
                                z.writestr(name, r["text"])
                return Response(200, blob.getvalue(),
                                {"Content-Type": "application/zip"})
        if sub == "tier":
            # Tier admin (cf. AddTierHandler/ListTierHandler,
            # cmd/admin-handlers-pools.go + tier config).
            tm = self.handlers.tier_mgr
            if tm is None:
                return j({"error": "tiering not enabled"}, 501)
            if method == "GET":
                st = tm.stats()
                return j({"tiers": tm.list_tiers(),
                          "usage": st["tiers"],
                          "journal_pending": st["journal_pending"]})
            if method == "DELETE":
                name = query.get("name", [""])[0]
                if not name:
                    raise S3Error("InvalidArgument", "name required")
                try:
                    removed = tm.remove_tier(name)
                except ValueError as e:
                    return j({"error": str(e)}, 409)
                if not removed:
                    return j({"error": f"no tier {name!r}"}, 404)
                return j({"ok": True})
            if method in ("POST", "PUT"):
                req_obj = _json.loads(body or b"{}")
                try:
                    name = req_obj["name"]
                    kind = req_obj.get("type", "fs")
                    if kind == "fs":
                        from ..bucket.tier import DirTierBackend
                        backend = DirTierBackend(req_obj["path"])
                    elif kind == "s3":
                        from ..bucket.tier import S3TierBackend
                        backend = S3TierBackend(
                            req_obj["endpoint"], req_obj["accessKey"],
                            req_obj["secretKey"], req_obj["bucket"])
                    elif kind == "pool":
                        # Second-local-pool tier: cold bucket on this
                        # deployment's own object layer.
                        from ..bucket.tier import PoolTierBackend
                        backend = PoolTierBackend(self.pools,
                                                  req_obj.get("bucket"))
                    else:
                        raise S3Error("InvalidArgument",
                                      f"unknown tier type {kind!r}")
                    # config persists the registration across restarts;
                    # duplicates are refused (409) — replacing a live
                    # tier's backend would orphan transitioned objects.
                    # PUT is the explicit credential-rotation path
                    # (cf. EditTierHandler, cmd/admin-handlers-pools.go).
                    cfg = {k: v for k, v in req_obj.items()
                           if k != "name"}
                    tm.add_tier(name, backend, config=cfg,
                                replace=(method == "PUT"))
                except KeyError as e:
                    raise S3Error("InvalidArgument",
                                  f"missing field {e}") from None
                except ValueError as e:
                    return j({"error": str(e)}, 409)
                return j({"ok": True})
        if sub == "ilm":
            # ILM plane: GET = stats (the crash harness polls
            # journal_pending to zero); POST = explicit transition
            # trigger / journal drain (what the scanner does on its own
            # cadence, made deterministic for tests and the matrix).
            tm = self.handlers.tier_mgr
            if tm is None:
                return j({"error": "tiering not enabled"}, 501)
            if method == "GET":
                return j(tm.stats())
            if method == "POST":
                req_obj = _json.loads(body or b"{}")
                if req_obj.get("op") == "drain":
                    freed = tm.drain_journal()
                    return j({"freed": freed,
                              "pending": tm.journal.pending()})
                bkt = req_obj.get("bucket")
                okey = req_obj.get("object")
                tname = req_obj.get("tier")
                if not bkt or not okey or not tname:
                    raise S3Error("InvalidArgument",
                                  "bucket, object, tier required")
                from ..storage.errors import StorageError as _SE
                try:
                    moved = tm.transition_object(
                        bkt, okey, tname,
                        req_obj.get("versionId", ""))
                except _SE as e:
                    from .api_errors import from_storage_error as _fse
                    raise _fse(e) from None
                return j({"transitioned": bool(moved)})
        if sub == "replication":
            # Replication plane: GET = pool stats (+ per-bucket resync
            # status with ?bucket=); POST op=resync starts/resumes a
            # bucket resync — the deterministic trigger the matrices
            # and bench drive (cf. ReplicationResync admin API).
            rp = self.handlers.replication
            if rp is None:
                return j({"error": "replication not enabled"}, 501)
            if method == "GET":
                out = rp.stats()
                bkt = query.get("bucket", [""])[0]
                if bkt:
                    out = dict(out)
                    out["resync"] = rp.resync_status(bkt)
                return j(out)
            if method == "POST":
                req_obj = _json.loads(body or b"{}")
                if req_obj.get("op") == "resync":
                    bkt = req_obj.get("bucket")
                    if not bkt:
                        raise S3Error("InvalidArgument",
                                      "bucket required")
                    return j(rp.start_resync(bkt))
                raise S3Error("InvalidArgument", "unknown op")
        if sub.startswith("inspect") and method == "GET":
            # Raw per-drive metadata download for debugging
            # (cf. InspectDataHandler, cmd/admin-handlers.go).
            bucket = query.get("volume", query.get("bucket", [""]))[0]
            obj = query.get("file", query.get("object", [""]))[0]
            if not bucket or not obj:
                raise S3Error("InvalidArgument", "volume and file required")
            copies = []
            for pi, pool in enumerate(self.pools.pools):
                for si, s in enumerate(getattr(pool, "sets", [pool])):
                    for di, d in enumerate(getattr(s, "drives", [])):
                        if d is None:
                            continue
                        try:
                            raw = d.read_all(bucket, f"{obj}/xl.meta")
                        except Exception:  # noqa: BLE001
                            continue
                        copies.append({"pool": pi, "set": si, "drive": di,
                                       "endpoint": getattr(d, "root", ""),
                                       "xl_meta_hex": raw.hex()})
            if not copies:
                return j({"error": "no xl.meta found"}, 404)
            return j({"volume": bucket, "file": obj, "copies": copies})
        if sub.startswith("kms"):
            # KMS admin (cf. KMSCreateKey/KMSKeyStatus handlers,
            # cmd/admin-router.go:40 + cmd/admin-handlers.go).
            kms = self.handlers.kms
            if kms is None:
                return j({"error": "KMS not configured"}, 501)
            if sub == "kms/status" and method == "GET":
                return j({"name": "StaticKMS",
                          "defaultKeyId": kms.key_id,
                          "endpoints": {"local": "online"}})
            if sub == "kms/key/list" and method == "GET":
                return j({"keys": kms.list_keys()})
            if sub == "kms/key/create" and method == "POST":
                key_id = query.get("key-id", [""])[0]
                if not key_id:
                    raise S3Error("InvalidArgument", "key-id required")
                from ..crypto.kms import KMSError
                try:
                    kms.create_key(key_id)
                except KMSError as e:
                    raise S3Error("InvalidArgument", str(e)) from None
                return j({"created": key_id})
            if sub == "kms/key/status" and method == "GET":
                key_id = query.get("key-id", [kms.key_id])[0]
                return j(kms.key_status(key_id))
            raise S3Error("MethodNotAllowed")
        if sub == "bandwidth" and method == "GET":
            # Per-bucket bandwidth over a sliding window
            # (cf. BandwidthMonitor admin route, cmd/admin-router.go).
            want = query.get("buckets", [""])[0]
            buckets = [b for b in want.split(",") if b] or None
            return j({"windowS": self.metrics.bandwidth.WINDOW,
                      "buckets": self.metrics.bandwidth.report(buckets)})
        if sub == "pools" and method == "GET":
            # Pool status listing (cf. ListPools,
            # cmd/admin-handlers-pools.go).
            out = []
            cap = {r["pool"]: r for r in self.pools.pool_status()}
            for pi, pool in enumerate(self.pools.pools):
                sets = getattr(pool, "sets", [pool])
                drives = online = 0
                for es in sets:
                    for d in getattr(es, "drives", []):
                        drives += 1
                        if d is not None and (not hasattr(d, "is_online")
                                              or d.is_online()):
                            online += 1
                row = {"pool": pi, "sets": len(sets),
                       "drivesPerSet": getattr(
                           sets[0], "n", drives) if sets else 0,
                       "drivesTotal": drives,
                       "drivesOnline": online,
                       "decommissioning": pi in self.pools.draining}
                crow = cap.get(pi, {})
                row["totalBytes"] = crow.get("total", 0)
                row["freeBytes"] = crow.get("free", 0)
                if "decommission" in crow:
                    row["decommission"] = crow["decommission"]
                out.append(row)
            return j({"pools": out,
                      "placement": self.pools.placement_pools()})
        if sub == "pool/add" and method == "POST":
            # Runtime expansion (cf. the reference's restart-time pool
            # add — here live): format + bootstrap the drives, lane
            # self-test, replicate the bucket set, THEN placement sees
            # it; no restart, new writes skew to the empty pool.
            req_obj = _json.loads(body or b"{}")
            spec = req_obj.get("drives", "")
            if not spec:
                raise S3Error("InvalidArgument",
                              "drives spec required (ellipses ok)")
            try:
                new_idx = self._pool_add(
                    spec, int(req_obj.get("setDriveCount", 0)) or None)
            except (ValueError, StorageError) as e:
                raise S3Error("InvalidArgument", str(e)) from None
            return j({"pool": new_idx,
                      "placement": self.pools.placement_pools()})
        if sub == "pool/decommission":
            # Drain lifecycle (cf. StartDecommission / Status /
            # Cancel, cmd/admin-handlers-pools.go).
            from ..background import decom as decom_mod
            q_pool = query.get("pool", [""])[0]
            if method == "GET":
                if q_pool:
                    d = self.pools.decommissions.get(int(q_pool))
                    if d is None:
                        return j({"error":
                                  f"no decommission for pool {q_pool}"},
                                 404)
                    return j(d.status())
                return j({"decommissions":
                          [self.pools.decommissions[i].status()
                           for i in sorted(self.pools.decommissions)]})
            if method != "POST":
                raise S3Error("MethodNotAllowed")
            if not q_pool:
                raise S3Error("InvalidArgument", "pool required")
            idx = int(q_pool)
            action = query.get("action", ["start"])[0]
            d = self.pools.decommissions.get(idx)
            if action == "start":
                if d is not None and d.state in ("draining", "paused"):
                    return j(d.status())         # idempotent start
                try:
                    d = decom_mod.Decommissioner(self.pools, idx)
                    d.start()
                except ValueError as e:
                    raise S3Error("InvalidArgument", str(e)) from None
            elif d is None:
                return j({"error": f"no decommission for pool {idx}"},
                         404)
            elif action == "pause":
                d.pause()
            elif action == "resume":
                d.resume()
            elif action == "cancel":
                d.cancel()
            else:
                raise S3Error("InvalidArgument",
                              f"unknown action {action!r}")
            self._propagate_topology()
            return j(d.status())
        if sub == "bucket-remote":
            # cmd/admin-bucket-targets handlers (SetRemoteTargetHandler
            # etc.): register the remote cluster/bucket a replication
            # config's rules flow to; persisted per bucket, reloaded at
            # boot with the rules.
            from ..bucket import replication as repl
            bucket = query.get("bucket", [""])[0]
            if not bucket:
                raise S3Error("InvalidArgument", "bucket required")
            raw = self.handlers.meta.get(bucket, "replication_targets")
            targets = repl.parse_targets(raw)
            if method == "GET":
                return j({"targets": [
                    {k: v for k, v in t.items() if k != "secretKey"}
                    for t in targets]})
            if method == "POST":
                req_obj = _json.loads(body or b"{}")
                try:
                    tb = req_obj["targetBucket"]
                    prev = next((t for t in targets
                                 if t.get("targetBucket") == tb), None)
                    kept = [t for t in targets
                            if t.get("targetBucket") != tb]
                    entry = {
                        # re-registering (credential rotation) KEEPS
                        # the ARN — a stale handle must stay valid
                        "arn": (prev["arn"] if prev else
                                f"arn:minio:replication::"
                                f"{len(kept) + 1}:{tb}"),
                        "endpoint": req_obj["endpoint"],
                        "accessKey": req_obj["accessKey"],
                        "secretKey": req_obj["secretKey"],
                        "targetBucket": tb,
                    }
                except KeyError as e:
                    raise S3Error("InvalidArgument", str(e)) from None
                targets = kept + [entry]
                self.handlers.meta.put(bucket, "replication_targets",
                                       _json.dumps(targets).encode())
                self._wire_replication(bucket)
                return j({"arn": entry["arn"]})
            if method == "DELETE":
                arn = query.get("arn", [""])[0]
                remaining = [t for t in targets if t.get("arn") != arn]
                if len(remaining) == len(targets):
                    return j({"error": f"no target with arn {arn!r}"},
                             404)
                self.handlers.meta.put(bucket, "replication_targets",
                                       _json.dumps(remaining).encode())
                # unwire NOW: replication to a deregistered target must
                # stop immediately, not at the next restart
                pool = (self.handlers.replication
                        if self.handlers else None)
                if pool is not None:
                    pool.unconfigure(bucket)
                    if remaining:
                        self._wire_replication(bucket)
                return j({"ok": True})
        if sub == "site-replication":
            sys_ = self._site_sys()
            if method == "GET":
                internal = query.get("internal", [""])[0]
                if internal == "deployment":
                    # join-handshake probe (validates reachability +
                    # credentials + deployment identity)
                    return j({"deploymentId": sys_.deployment_id,
                              "enabled": sys_.enabled})
                if internal == "digest":
                    return j(sys_.local_digest())
                legacy = self.site_replicator
                if not sys_.enabled and legacy is not None:
                    return j({"enabled": True,
                              "sites": [{"name": p.name,
                                         "endpoint": p.endpoint}
                                        for p in legacy.peers]})
                info = {"enabled": sys_.enabled,
                        "groupId": sys_.state.get("group_id", ""),
                        "sites": [{"name": s["name"],
                                   "endpoint": s["endpoint"],
                                   "deploymentId": s["deploymentId"]}
                                  for s in sys_.state.get("sites", [])]}
                return j(info)
            if method == "POST":
                from ..storage.errors import StorageError as _SE
                req_obj = _json.loads(body or b"{}")
                action = req_obj.get("action", "")
                try:
                    if action == "add":
                        return j(sys_.add_peers(req_obj["sites"]))
                    if action == "join":
                        sys_.accept_join(req_obj["state"])
                        return j({"ok": True})
                    if action == "status":
                        return j(sys_.status())
                    if action == "reconcile":
                        return j(sys_.reconcile())
                    if action == "remove":
                        return j(sys_.remove_site(req_obj["site"]))
                    if action == "leave":
                        sys_.accept_leave()
                        return j({"ok": True})
                except _SE as e:
                    return j({"error": str(e)}, 409)
                except KeyError as e:
                    raise S3Error("InvalidArgument", str(e)) from None
                raise S3Error("InvalidArgument",
                              f"unknown action {action!r}")
        if sub == "service" and method == "POST":
            # Real semantics (cf. ServiceHandler, cmd/admin-handlers.go):
            # stop/restart shut the listener down after this response
            # flushes; the CLI serve loop (server/__main__.py) re-builds
            # the server when service_event == "restart".
            action = query.get("action", ["status"])[0]
            if action == "status":
                return j({"action": "status",
                          "serviceEvent": self.service_event,
                          "at": _time.time()})
            if action not in ("restart", "stop"):
                raise S3Error("InvalidArgument",
                              f"unknown service action {action!r}")
            self.service_event = action
            import threading as _threading

            def _later():
                _time.sleep(0.25)        # let the response flush
                # Same drain as SIGTERM: inflight requests finish,
                # heal/MRF state checkpoints, THEN the listener drops.
                self.drain()
                self.shutdown()
            _threading.Thread(target=_later, daemon=True).start()
            return j({"action": action, "acknowledged": True,
                      "at": _time.time()})
        raise S3Error("MethodNotAllowed",
                      f"unknown admin endpoint {sub!r}")

    def _span_stream(self, tracer, filt, max_s: float,
                     poll: float = 0.05):
        """Generator behind POST /minio/admin/v3/trace: drain the span
        PubSub, apply server-side filters, frame as NDJSON. Subscribing
        is what turns tracing on — requests arriving while at least one
        stream is open get real span trees."""
        import json as _json
        import time as _time
        q = tracer.subscribe(2000)
        self._began.pop(threading.get_ident(), None)    # no stall: a stream
        try:
            deadline = (_time.monotonic() + max_s) if max_s > 0 else None
            last = _time.monotonic()
            while deadline is None or _time.monotonic() < deadline:
                sent = False
                while q:
                    rec = q.popleft()
                    if filt.matches(rec):
                        yield _json.dumps(rec).encode() + b"\n"
                        sent = True
                now = _time.monotonic()
                if sent:
                    last = now
                elif now - last > 5.0:
                    # Keepalive blank line: NDJSON consumers skip it,
                    # and the write is how we notice a client hangup.
                    yield b"\n"
                    last = now
                _time.sleep(poll)
        finally:
            tracer.unsubscribe(q)

    def _listen_response(self, bucket: str, query: dict) -> Response:
        """ListenNotification: `GET /{bucket}?events=...` (and the
        minio extension `GET /minio/listen` with bucket="") as a
        chunked NDJSON stream of live S3 event records (cf.
        ListenNotificationHandler, cmd/bucket-notification-handlers.go).
        `duration` (seconds) bounds the stream for polling clients."""
        notify = getattr(self.handlers, "notify", None)
        if notify is None or not hasattr(notify, "subscribe_events"):
            raise S3Error("NotImplemented", "notifications not enabled")
        if bucket and not self.pools.bucket_exists(bucket):
            raise S3Error("NoSuchBucket", bucket)
        prefix = query.get("prefix", [""])[0]
        suffix = query.get("suffix", [""])[0]
        names = [n for ns in query.get("events", [])
                 for n in ns.split(",") if n]
        try:
            max_s = float(query.get("duration", ["0"])[0] or 0)
        except ValueError:
            max_s = 0.0
        return Response(
            200, b"",
            {"Content-Type": "application/x-ndjson",
             "Transfer-Encoding": "chunked"},
            body_iter=self._listen_stream(notify, bucket, prefix,
                                          suffix, names, max_s))

    def _listen_stream(self, notify, bucket, prefix, suffix, names,
                       max_s: float, poll: float = 0.05):
        import json as _json
        import time as _time
        from fnmatch import fnmatch
        q = notify.subscribe_events(2000)
        self._began.pop(threading.get_ident(), None)    # no stall: a stream
        try:
            deadline = (_time.monotonic() + max_s) if max_s > 0 else None
            last = _time.monotonic()
            while deadline is None or _time.monotonic() < deadline:
                sent = False
                while q:
                    ev = q.popleft()
                    if bucket and ev["bucket"] != bucket:
                        continue
                    key = ev["key"]
                    if prefix and not key.startswith(prefix):
                        continue
                    if suffix and not key.endswith(suffix):
                        continue
                    if names and not any(fnmatch(ev["eventName"], pat)
                                         for pat in names):
                        continue
                    yield _json.dumps(
                        {"Records": [ev["record"]]}).encode() + b"\n"
                    sent = True
                now = _time.monotonic()
                if sent:
                    last = now
                elif now - last > 5.0:
                    yield b"\n"
                    last = now
                _time.sleep(poll)
        finally:
            notify.unsubscribe_events(q)

    def _dispatch_internal(self, req, path: str, query: dict) -> Response:
        """Unauthenticated infra endpoints: health + metrics
        (cf. cmd/metrics-router.go:46, cmd/healthcheck-handler.go)."""
        import json as _json

        from ..observe.health import cluster_health
        if path == "/minio/health/live":
            return Response(200)
        if path == "/minio/health/ready":
            # ready = object layer bound (cluster boot done), the boot
            # ladder built, AND not draining — load balancers stop
            # routing here first.
            if self.warming and not self._asking:
                from ..ops import coalesce
                self.warming = not coalesce.ladder_idle()
            if self.draining or self.warming:
                return Response(503, headers={"Retry-After": "1"})
            # `handlers` is bound last (bind_object_layer): S3 requests
            # answer ServerNotInitialized until then, so ready waits too.
            return Response(200 if self.handlers is not None else 503)
        if self.pools is None:
            return Response(503)
        if path == "/minio/health/cluster":
            maint = int(query.get("maintenance", ["0"])[0] or 0)
            ok, detail = cluster_health(self.pools, maint)
            return Response(200 if ok else 503,
                            _json.dumps(detail).encode(),
                            {"Content-Type": "application/json"})
        if path in ("/minio/v2/metrics/cluster", "/minio/v2/metrics/node"):
            return Response(200, self.local_metrics_text().encode(),
                            {"Content-Type": "text/plain; version=0.0.4"})
        raise S3Error("MethodNotAllowed")

    # -- observability plane (audit fan-out, node snapshots, fleet merge) ----

    def _emit_audit(self, **kw) -> None:
        """Build one structured audit entry and fan it to every
        configured target.  Never blocks and never raises into the
        request path: targets shed to their drop counters."""
        if not self.audit_targets:
            return
        from ..observe.audit import build_entry
        entry = build_entry(node=f"{self.host}:{self.port}",
                            worker=self.worker_id, **kw)
        for t in self.audit_targets:
            try:
                t.send(entry)
            except Exception:  # noqa: BLE001 — a sink bug can't 500 a request
                pass
        if (self.worker_plane is not None
                and self.worker_id is not None):
            # Mirror this worker's shed count into the shared slab so
            # the pool owner's scrape aggregates drops across workers.
            self.worker_plane.state.set_audit_dropped(
                self.worker_id,
                sum(t.dropped for t in self.audit_targets))

    def _qos_bucket_rate(self, bucket: str) -> float:
        """Per-bucket bandwidth budget (bytes/s) from the bucket quota
        config, cached ~5s so the request path never pays a metadata
        read per GET (0 = unlimited / no config)."""
        import time as _time
        now = _time.monotonic()
        hit = self._qos_bw_cache.get(bucket)
        if hit is not None and now - hit[1] < 5.0:
            return hit[0]
        rate = 0.0
        if self.handlers is not None:
            try:
                raw = self.handlers.meta.get(bucket, "quota")
                if raw is not None:
                    from ..bucket.quota import parse_quota_config
                    rate = float(
                        parse_quota_config(raw).get("bandwidth", 0))
            except Exception:  # noqa: BLE001 — bad config ≠ blocked IO
                rate = 0.0
        self._qos_bw_cache[bucket] = (rate, now)
        return rate

    def local_metrics_text(self) -> str:
        """THIS node's full Prometheus render — the single-node body of
        /minio/v2/metrics/node and the peer.metrics_text RPC verb the
        cluster aggregate fans out to.  Scrape discipline: everything
        here is a copy-free read of counters other planes already
        maintain — no device state is touched, no dispatcher lock is
        taken (the coalescer/digest numbers come from DATA_PATH's
        monotonic tallies, not from live lane introspection)."""
        from ..rpc import rest as _rest

        # Belt and braces for the "never block" contract: remote-drive
        # capacity reads are cached (storage_rpc._DISK_INFO_TTL_S), but a
        # COLD cache against a blackholed peer would still pay one RPC
        # timeout per drive.  A short ambient deadline turns that worst
        # case into a bounded sub-second fail-fast.
        left = _rest.deadline_remaining()
        tok = _rest.set_deadline(1.0 if left is None else min(1.0, left))
        try:
            if self.pools is not None:
                self.metrics.update_cluster(self.pools, self.scanner,
                                            self.handlers.tier_mgr)
            if self.cluster_node is not None:
                self.metrics.update_peers(
                    self.cluster_node.peer_clients.values())
        finally:
            _rest.clear_deadline(tok)
        self.metrics.update_audit(self.audit_targets)
        self.metrics.update_qos(self.qos if _qos.qos_enabled()
                                else None)
        self.metrics.update_replication(
            self.handlers.replication if self.handlers else None)
        text = self.metrics.render()
        if self.worker_plane is not None:
            # Pool aggregates live in shared slabs, so WHICHEVER
            # worker the kernel picked exports the same pool-wide
            # view (worker liveness, arena, rings, owner).
            text += self.worker_plane.render_prom()
        return text

    def local_healthinfo(self) -> dict:
        """One node's health document (the cmd/admin-handlers.go
        HealthInfo role): drive/breaker states, peer liveness,
        pool/decom status, MRF backlog, device-lane depths,
        digest/coalescer occupancy, drain state, worker slab, audit
        sink health — all composed from state other planes already
        maintain, msgpack/JSON-safe for the peer fan-out."""
        import time as _time

        from ..observe.metrics import DATA_PATH
        drives: list[dict] = []
        pool_rows: list = []
        mrf_rows: list[dict] = []
        if self.pools is not None:
            seen_mrf: set[int] = set()
            for pi, pool in enumerate(self.pools.pools):
                sets = getattr(pool, "sets", None) or [pool]
                for si, es in enumerate(sets):
                    for di, d in enumerate(getattr(es, "drives", [])):
                        if d is None:
                            state = "offline"
                        elif hasattr(d, "health_state"):
                            state = d.health_state()
                        elif (hasattr(d, "is_online")
                                and not d.is_online()):
                            state = "offline"
                        else:
                            state = "ok"
                        drives.append({"pool": pi, "set": si,
                                       "drive": di, "state": state})
                    mrf = getattr(es, "mrf", None)
                    if (mrf is not None and id(mrf) not in seen_mrf
                            and hasattr(mrf, "stats")):
                        seen_mrf.add(id(mrf))
                        mrf_rows.append({"pool": pi, "set": si,
                                         **mrf.stats()})
            if hasattr(self.pools, "pool_status"):
                from ..rpc import rest as _rest
                left = _rest.deadline_remaining()
                tok = _rest.set_deadline(
                    1.0 if left is None else min(1.0, left))
                try:
                    pool_rows = self.pools.pool_status()
                except Exception:  # noqa: BLE001 — status is best-effort
                    pool_rows = []
                finally:
                    _rest.clear_deadline(tok)
        lanes: dict = {}
        try:
            from ..ops import coalesce as _co
            lanes = {str(k): v
                     for k, v in _co.get().lane_stats().items()}
        except Exception:  # noqa: BLE001 — lanes are best-effort
            lanes = {}
        snap = DATA_PATH.snapshot()
        digest = {k: snap[k] for k in snap
                  if k.startswith("dg_") and not isinstance(snap[k],
                                                            dict)}
        coalescer = {k: snap[k] for k in snap
                     if k.startswith("co_") and not isinstance(snap[k],
                                                               dict)}
        peers = (self.cluster_node.peer_info()
                 if self.cluster_node is not None else [])
        workers = (self.worker_plane.workers_info()
                   if self.worker_plane is not None else None)
        tier = getattr(self.pools, "hot_tier", None)
        devcache_stats = None
        h2d_row: dict = {}
        try:
            from ..ops import devcache as _devcache
            devcache_stats = _devcache.stats()
            h2d = _devcache.h2d_stats()
            h2d_row = {"bytes": h2d["h2d_bytes"],
                       "dispatches": h2d["h2d_dispatches"],
                       "lanes": {str(k): v
                                 for k, v in h2d["lanes"].items()}}
        except Exception:  # noqa: BLE001 — h2d ledger is best-effort
            pass
        from ..ops import devices as _devices
        return {
            "endpoint": f"{self.host}:{self.port}",
            # What the shard math runs on, as the process that holds
            # the devices found it (the boot line says the same; a pool
            # worker reports its owner's answer, in_process false).
            "device": _devices.describe(),
            "time": round(_time.time(), 3),
            "draining": bool(self.draining),
            "inflight": int(self._inflight),
            "drives": drives,
            "pools": pool_rows,
            "mrf": mrf_rows,
            "peers": peers,
            "device_lanes": lanes,
            "digest": digest,
            "coalescer": coalescer,
            "workers": workers,
            "hotcache": tier.stats() if tier is not None else None,
            "devcache": devcache_stats,
            "h2d": h2d_row,
            "ilm": (self.handlers.tier_mgr.stats()
                    if self.handlers.tier_mgr is not None else None),
            "replication": (self.handlers.replication.stats()
                            if self.handlers.replication is not None
                            else None),
            "audit": [t.stats() for t in self.audit_targets],
            "slo": (self.metrics.last_minute.snapshot()
                    if self.slo_enabled else {}),
            "qos": (self.qos.stats() if _qos.qos_enabled() else
                    {"enabled": False}),
        }

    def _obs_fanout(self, verb: str) -> tuple[dict, dict]:
        """Run one obs RPC verb (peer.metrics_text / peer.healthinfo)
        against every peer under a single wall-clock budget
        (MTPU_OBS_DEADLINE_MS).  Breaker-aware: an offline peer is
        node_up 0 immediately (no dial); a hung one costs at most the
        remaining budget — the aggregate NEVER hangs the scrape.
        Returns ({node: payload}, {node: 0|1}), this node included."""
        import time as _time
        from concurrent.futures import ThreadPoolExecutor

        from ..rpc import rest as _rest
        me = f"{self.host}:{self.port}"
        local = (self.local_metrics_text() if verb == "metrics_text"
                 else self.local_healthinfo())
        results: dict = {me: local}
        node_up: dict = {me: 1}
        node = self.cluster_node
        if node is None or not node.peer_clients:
            return results, node_up
        try:
            budget_s = float(_os.environ.get("MTPU_OBS_DEADLINE_MS",
                                             "8000") or 8000) / 1e3
        except ValueError:
            budget_s = 8.0
        deadline = _time.monotonic() + budget_s
        key = "text" if verb == "metrics_text" else "info"

        def one(cli):
            if not cli.is_online():
                return None          # breaker open: fast-fail, no dial
            left = deadline - _time.monotonic()
            if left <= 0:
                return None
            # Arm the RPC deadline contextvar in THIS worker thread so
            # rest.py clamps the hop's timeout to the remaining budget.
            tok = _rest.set_deadline(left)
            try:
                out = cli.call(f"peer.{verb}", {}, idempotent=True)
                return out.get(key) if isinstance(out, dict) else None
            except Exception:  # noqa: BLE001 — dead peer == node_up 0
                return None
            finally:
                _rest.clear_deadline(tok)

        peers = [(f"{h}:{p}", cli)
                 for (h, p), cli in node.peer_clients.items()]
        # No context manager: shutdown(wait=False) below — waiting for
        # a hung future would defeat the deadline budget.
        ex = ThreadPoolExecutor(max_workers=len(peers),
                                thread_name_prefix="obs-fanout")
        futs = [(name, ex.submit(one, cli)) for name, cli in peers]
        for name, fut in futs:
            try:
                out = fut.result(
                    timeout=max(0.0, deadline - _time.monotonic()))
            except Exception:  # noqa: BLE001 — budget exhausted
                out = None
            if out is None:
                node_up[name] = 0
            else:
                node_up[name] = 1
                results[name] = out
        ex.shutdown(wait=False)
        return results, node_up

    def _dispatch(self, req, path: str, query: dict) -> Response:
        with ospan.span("http.auth"):
            if self._stream_eligible(req.command, path, query):
                body, access_key = self._authenticate_streaming(
                    req, path, query)
            else:
                body, access_key = self._authenticate(req, path, query)
        # Auth succeeded and routing begins: stamp the audit identity.
        # A request that raised before this point audits with a null
        # object and an empty accessKey (rejected pre-dispatch).
        req.audit_access_key = access_key
        req.audit_dispatched = True
        h = self.handlers
        method = req.command
        # Internal replication marker: only principals allowed to
        # replicate may present it — any other writer could mark its
        # objects REPLICA and silently exempt them from replication
        # (the reference strips this internal header the same way,
        # gated on ReplicateObjectAction). Must happen BEFORE the
        # header dict below is captured for the handlers.
        if not self._may_replicate(access_key):
            for hk in ("x-amz-replication-status",
                       "x-mtpu-repl-version-id", "x-mtpu-repl-mtime"):
                if req.headers.get(hk):
                    del req.headers[hk]
        headers = {k: v for k, v in req.headers.items()}

        if path.startswith("/minio/admin/"):
            return self._dispatch_admin(access_key, method, path, query,
                                        body)
        if path == "/minio/listen":
            # Cluster-wide listen (minio extension): admin-plane
            # authorization, then the same event stream with no bucket
            # restriction.
            self._admin_authorize(access_key, "listen", method)
            return self._listen_response("", query)

        # Per-tenant QoS (post-auth — the VERIFIED identity throttles,
        # unlike the admission peek): req/s token bucket plus a
        # positive-balance check on the post-paid bandwidth bucket.
        # Both short-circuit unless the tenant's class has rates
        # configured, so the oracle path costs one env read.
        if _qos.qos_enabled() and access_key:
            klass = _qos.tenant_class(access_key)
            if not self.qos.tenant_admit(access_key, klass):
                raise S3Error("SlowDown",
                              "per-tenant request rate exceeded")
            if not self.qos.tenant_bw_ok(access_key, klass):
                raise S3Error("SlowDown",
                              "per-tenant bandwidth budget exceeded")

        parts = path.lstrip("/").split("/", 1)
        bucket = parts[0] if parts[0] else ""
        key = parts[1] if len(parts) > 1 else ""

        # Per-bucket bandwidth budget (the `bandwidth` field of the
        # quota config — cmd/bucket-quota.go enforcement riding the
        # same config object as the hard quota).
        if _qos.qos_enabled() and bucket:
            rate = self._qos_bucket_rate(bucket)
            if rate > 0 and not self.qos.bucket_bw_ok(bucket, rate):
                raise S3Error("SlowDown",
                              f"bucket {bucket} bandwidth budget "
                              "exceeded")

        # Federation: a request for a bucket another cluster owns
        # redirects there (the bucket-DNS role, cmd/etcd.go +
        # internal/config/dns — clients normally resolve
        # bucket.domain straight to the owner; the redirect covers
        # clients that hit the wrong cluster). Bucket CREATION is
        # handled in make_bucket (global-uniqueness check).
        if (bucket and self.bucket_dns is not None
                and not (method == "PUT" and not key)
                and self.pools is not None
                and not self.pools.bucket_exists(bucket)):
            try:
                owner = self.bucket_dns.owner_endpoint(bucket)
            except Exception:  # noqa: BLE001 — etcd down: serve local
                owner = None
            if owner:
                # Preserve the FULL request target: dropping the query
                # would turn a versioned delete or multipart call into
                # a different operation on the owner.
                qs = urllib.parse.urlencode(
                    [(k, v) for k, vs in query.items() for v in vs])
                loc = f"{owner}{urllib.parse.quote(path)}" + \
                    (f"?{qs}" if qs else "")
                return Response(307, b"",
                                {"Location": loc, "Content-Length": "0"})

        if self.trace_sink is not None:
            self.trace_sink({"method": method, "path": path,
                             "query": {k: v[0] for k, v in query.items()}})

        if not bucket:
            if method == "POST":
                return self._handle_sts(access_key, headers, body,
                                        req=req)
            if method == "GET":
                self._authorize(access_key, method, "", "", query,
                                req.client_address[0])
                return h.list_buckets()
            raise S3Error("MethodNotAllowed")

        ctype = headers.get("Content-Type", headers.get("content-type", ""))
        form_post = (method == "POST" and not key and "delete" not in query
                     and ctype.startswith("multipart/form-data"))
        if not form_post:
            # Browser form posts carry their own signed POST policy;
            # _handle_post_upload authenticates + authorizes from the form.
            self._authorize(access_key, method, bucket, key, query,
                            req.client_address[0])
        if not key:
            return self._dispatch_bucket(method, bucket, query, headers,
                                         body, access_key)
        return self._dispatch_object(method, bucket, key, query, headers,
                                     body)

    # -- STS (cf. cmd/sts-handlers.go:99 AssumeRole) -------------------------

    def _handle_sts(self, access_key: str, headers: dict,
                    body: bytes, req=None) -> Response:
        import json
        import urllib.parse as up
        import xml.etree.ElementTree as ET
        import datetime as dt

        form = up.parse_qs(body.decode("utf-8", "replace"))
        action = form.get("Action", [""])[0]
        if action == "AssumeRoleWithWebIdentity":
            return self._handle_sts_web_identity(form)
        if action == "AssumeRoleWithClientGrants":
            # Same OIDC token flow, legacy field names
            # (cf. AssumeRoleWithClientGrants, cmd/sts-handlers.go:99).
            return self._handle_sts_web_identity(
                form, token_field="Token",
                action_name="AssumeRoleWithClientGrants")
        if action == "AssumeRoleWithLDAPIdentity":
            return self._handle_sts_ldap(form)
        if action == "AssumeRoleWithCertificate":
            return self._handle_sts_certificate(form, req)
        if action != "AssumeRole":
            raise S3Error("NotImplemented", "unknown STS action")
        if self.iam is None:
            raise S3Error("NotImplemented", "IAM is not enabled")
        if access_key == "":
            raise S3Error("AccessDenied", "AssumeRole must be signed")
        if access_key == self.creds.access_key:
            from ..iam.iam import Identity
            parent = Identity(access_key=access_key,
                              secret_key=self.creds.secret_key,
                              kind="root")
        else:
            parent = self.iam.lookup(access_key)
            if parent is None or parent.kind == "sts":
                raise S3Error("AccessDenied", "cannot assume from here")
        try:
            duration = int(form.get("DurationSeconds", ["3600"])[0])
        except ValueError:
            raise S3Error("InvalidArgument",
                          "DurationSeconds must be an integer") from None
        policy_doc = None
        if form.get("Policy", [""])[0]:
            try:
                policy_doc = json.loads(form["Policy"][0])
            except ValueError:
                raise S3Error("MalformedXML", "bad inline policy") from None
        ident = self.iam.assume_role(parent, duration, policy_doc)
        exp = dt.datetime.fromtimestamp(
            ident.expiration, dt.timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ")
        ns = "https://sts.amazonaws.com/doc/2011-06-15/"
        root = ET.Element("AssumeRoleResponse", xmlns=ns)
        result = ET.SubElement(root, "AssumeRoleResult")
        c = ET.SubElement(result, "Credentials")
        for tag, val in (("AccessKeyId", ident.access_key),
                         ("SecretAccessKey", ident.secret_key),
                         ("SessionToken", ident.session_token),
                         ("Expiration", exp)):
            e = ET.SubElement(c, tag)
            e.text = val
        xml_body = (b'<?xml version="1.0" encoding="UTF-8"?>'
                    + ET.tostring(root, encoding="unicode").encode())
        return Response(200, xml_body,
                        {"Content-Type": "application/xml"})

    @staticmethod
    def _sts_credentials_xml(action: str, ident) -> Response:
        import datetime as dt
        import xml.etree.ElementTree as ET
        exp = dt.datetime.fromtimestamp(
            ident.expiration, dt.timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ")
        ns = "https://sts.amazonaws.com/doc/2011-06-15/"
        root = ET.Element(f"{action}Response", xmlns=ns)
        result = ET.SubElement(root, f"{action}Result")
        c = ET.SubElement(result, "Credentials")
        for tag, val in (("AccessKeyId", ident.access_key),
                         ("SecretAccessKey", ident.secret_key),
                         ("SessionToken", ident.session_token),
                         ("Expiration", exp)):
            e = ET.SubElement(c, tag)
            e.text = val
        xml_body = (b'<?xml version="1.0" encoding="UTF-8"?>'
                    + ET.tostring(root, encoding="unicode").encode())
        return Response(200, xml_body,
                        {"Content-Type": "application/xml"})

    def _handle_sts_web_identity(
            self, form: dict, token_field: str = "WebIdentityToken",
            action_name: str = "AssumeRoleWithWebIdentity") -> Response:
        """AssumeRoleWithWebIdentity / AssumeRoleWithClientGrants:
        token-authenticated (unsigned) STS (cf. cmd/sts-handlers.go:48-115
        — ClientGrants is the same OIDC validation with legacy naming)."""
        from ..iam.iam import Identity
        from ..iam.oidc import OIDCError
        if self.iam is None or getattr(self, "oidc", None) is None:
            raise S3Error("NotImplemented", "OIDC is not configured")
        token = form.get(token_field, [""])[0]
        if not token:
            raise S3Error("InvalidArgument", f"missing {token_field}")
        try:
            claims = self.oidc.validate(token)
        except OIDCError as e:
            raise S3Error("AccessDenied", f"token rejected: {e}") from None
        policies = self.oidc.policies_from(claims)
        if not policies:
            raise S3Error("AccessDenied", "token grants no policies")
        parent = Identity(access_key=f"oidc:{claims.get('sub', 'unknown')}",
                          secret_key="", kind="user", policies=policies)
        try:
            duration = int(form.get("DurationSeconds", ["3600"])[0])
        except ValueError:
            raise S3Error("InvalidArgument",
                          "DurationSeconds must be an integer") from None
        ident = self.iam.assume_role(parent, duration)
        return self._sts_credentials_xml(action_name, ident)

    def _handle_sts_ldap(self, form: dict) -> Response:
        """AssumeRoleWithLDAPIdentity: directory-authenticated STS
        (cf. cmd/sts-handlers.go LDAP flow + internal/config/identity/
        ldap). The LDAP client binds as the user — the directory is
        the credential check — and the user's groups map to IAM
        policies."""
        from ..iam.iam import Identity
        from ..iam.ldap import LDAPError
        if self.iam is None or self.ldap is None:
            raise S3Error("NotImplemented", "LDAP is not configured")
        username = form.get("LDAPUsername", [""])[0]
        password = form.get("LDAPPassword", [""])[0]
        if not username or not password:
            raise S3Error("InvalidArgument",
                          "LDAPUsername and LDAPPassword required")
        try:
            user_dn, policies = self.ldap.authenticate(username, password)
        except LDAPError as e:
            raise S3Error("AccessDenied",
                          f"LDAP authentication failed: {e}") from None
        except OSError as e:
            # directory unreachable: an operational condition, not a
            # handler crash
            raise S3Error("ServiceUnavailable",
                          f"LDAP directory unreachable: {e}") from None
        if not policies:
            raise S3Error("AccessDenied",
                          "LDAP identity grants no policies")
        parent = Identity(access_key=f"ldap:{user_dn}", secret_key="",
                          kind="user", policies=policies)
        try:
            duration = int(form.get("DurationSeconds", ["3600"])[0])
        except ValueError:
            raise S3Error("InvalidArgument",
                          "DurationSeconds must be an integer") from None
        ident = self.iam.assume_role(parent, duration)
        return self._sts_credentials_xml("AssumeRoleWithLDAPIdentity",
                                         ident)

    def _handle_sts_certificate(self, form: dict, req) -> Response:
        """AssumeRoleWithCertificate: mTLS-authenticated STS
        (cf. cmd/sts-handlers.go:115 + internal/config/identity/tls).
        The TLS layer already verified the client certificate against
        the configured CA (client_ca); per the reference's convention
        the certificate's CN names the IAM policy the credentials
        carry."""
        from ..iam.iam import Identity
        if self.iam is None:
            raise S3Error("NotImplemented", "IAM is not enabled")
        cert = None
        if req is not None:
            getpeer = getattr(req.connection, "getpeercert", None)
            if getpeer is not None:
                cert = getpeer()
        if not cert:
            raise S3Error("AccessDenied",
                          "a verified TLS client certificate is required")
        cn = ""
        for rdn in cert.get("subject", ()):
            for key, val in rdn:
                if key == "commonName":
                    cn = val
        if not cn:
            raise S3Error("AccessDenied", "client certificate has no CN")
        # Fail loudly at STS time when the CN names no policy —
        # zero-permission credentials would surface as baffling
        # downstream denials (the LDAP flow enforces the same).
        if cn not in self.iam.list_policies():
            raise S3Error("AccessDenied",
                          f"no IAM policy named {cn!r} for this "
                          "certificate")
        parent = Identity(access_key=f"tls:{cn}", secret_key="",
                          kind="user", policies=[cn])
        try:
            duration = int(form.get("DurationSeconds", ["3600"])[0])
        except ValueError:
            raise S3Error("InvalidArgument",
                          "DurationSeconds must be an integer") from None
        ident = self.iam.assume_role(parent, duration)
        return self._sts_credentials_xml("AssumeRoleWithCertificate",
                                         ident)

    def _handle_post_upload(self, bucket: str, content_type: str,
                            body: bytes) -> Response:
        """Browser form upload (cf. PostPolicyBucketHandler).

        Auth rides in the form itself (signed POST policy), so this is
        reached through the anonymous path and re-authenticated here.
        """
        from . import postpolicy as pp
        fields = pp.parse_multipart_form(content_type, body)
        file_data, _ = fields.get("file", (b"", ""))
        key = fields.get("key", (b"", ""))[0].decode("utf-8", "replace")
        if not key:
            raise S3Error("InvalidArgument", "missing key field")
        key = key.replace("${filename}", fields.get("file", (b"", ""))[1])
        access_key = pp.verify_post_signature(self._lookup_creds, fields)
        pp.check_post_policy(fields["policy"][0], fields, len(file_data),
                             bucket=bucket)
        self._authorize(access_key, "PUT", bucket, key, {})
        headers = {}
        ct = fields.get("content-type")
        if ct:
            headers["Content-Type"] = ct[0].decode("utf-8", "replace")
        resp = self.handlers.put_object(bucket, key, file_data, headers)
        resp.status = 204
        return resp

    def _delete_authorizer(self, access_key: str, bucket: str):
        """Per-key authorization closure for multi-object delete."""
        if access_key == self.creds.access_key:
            return None                          # root: no per-key checks
        if access_key == "":
            # Anonymous: each key needs a bucket-policy DeleteObject
            # grant — a Put-only public bucket must not allow deletes.
            from ..iam.policy import Policy, PolicyError
            data = self.handlers.meta.get(bucket, "policy")
            pol_obj = None
            if data is not None:
                try:
                    pol_obj = Policy(data.decode())
                except (PolicyError, ValueError):
                    pol_obj = None

            def can_anon(key: str, version_id: str) -> bool:
                if pol_obj is None:
                    return False
                action = ("s3:DeleteObjectVersion" if version_id
                          else "s3:DeleteObject")
                return pol_obj.is_allowed(action, f"{bucket}/{key}",
                                          principal="*")
            return can_anon
        if self.iam is None:
            return lambda key, version_id: False
        ident = self.iam.lookup(access_key)

        def can_delete(key: str, version_id: str) -> bool:
            if ident is None:
                return False
            action = ("s3:DeleteObjectVersion" if version_id
                      else "s3:DeleteObject")
            return self.iam.is_allowed(ident, action, f"{bucket}/{key}")
        return can_delete

    def _dispatch_bucket(self, method, bucket, query, headers,
                         body, access_key="") -> Response:
        h = self.handlers
        config_sub = next((s for s in h._CONFIG_KINDS
                           if s in query and s != "versioning"), None)
        if method == "PUT":
            if "versioning" in query:
                return h.put_bucket_versioning(bucket, body)
            if config_sub:
                return h.put_bucket_config(bucket, config_sub, body)
            return h.make_bucket(bucket)
        if method == "HEAD":
            return h.head_bucket(bucket)
        if method == "DELETE":
            if config_sub:
                return h.delete_bucket_config(bucket, config_sub)
            return h.delete_bucket(bucket)
        if method == "POST":
            if "delete" in query:
                return h.delete_objects(
                    bucket, body,
                    can_delete=self._delete_authorizer(access_key, bucket))
            ctype = headers.get("Content-Type",
                                headers.get("content-type", ""))
            if ctype.startswith("multipart/form-data"):
                return self._handle_post_upload(bucket, ctype, body)
            raise S3Error("MethodNotAllowed")
        if method == "GET":
            if "events" in query:
                # ListenBucketNotification: the `events` query is what
                # distinguishes the live stream from the stored
                # `?notification` config (the reference registers the
                # listen route with Queries("events", ...)).
                return self._listen_response(bucket, query)
            if "location" in query:
                return h.get_bucket_location(bucket)
            if "versioning" in query:
                return h.get_bucket_versioning(bucket)
            if config_sub:
                return h.get_bucket_config(bucket, config_sub)
            if "uploads" in query:
                return h.list_multipart_uploads(bucket, query)
            if "versions" in query:
                return h.list_object_versions(bucket, query)
            return h.list_objects(bucket, query)
        raise S3Error("MethodNotAllowed")

    def _dispatch_object(self, method, bucket, key, query, headers,
                         body) -> Response:
        h = self.handlers
        if method == "PUT":
            if "partNumber" in query and "uploadId" in query:
                return h.put_part(bucket, key, query, body, headers)
            if "tagging" in query:
                return h.put_object_tagging(bucket, key, query, body)
            if "retention" in query:
                return h.put_object_retention(bucket, key, query, body,
                                              headers)
            if "legal-hold" in query:
                return h.put_object_legal_hold(bucket, key, query, body)
            return h.put_object(bucket, key, body, headers)
        if method == "GET":
            if "uploadId" in query:
                return h.list_parts(bucket, key, query)
            if "tagging" in query:
                return h.get_object_tagging(bucket, key, query)
            if "retention" in query:
                return h.get_object_retention(bucket, key, query)
            if "legal-hold" in query:
                return h.get_object_legal_hold(bucket, key, query)
            return h.get_object(bucket, key, query, headers)
        if method == "HEAD":
            return h.get_object(bucket, key, query, headers, head=True)
        if method == "DELETE":
            if "uploadId" in query:
                return h.abort_multipart(bucket, key, query)
            return h.delete_object(bucket, key, query, headers)
        if method == "POST":
            if "restore" in query:
                if h.tier_mgr is None:
                    raise S3Error("NotImplemented", "tiering not enabled")
                # <RestoreRequest><Days>N</Days></RestoreRequest> makes
                # the restore TEMPORARY (x-amz-restore semantics, the
                # scanner re-expires it); an empty body restores
                # permanently (the pre-existing behaviour).
                days = None
                if body:
                    import xml.etree.ElementTree as _ET
                    try:
                        root = _ET.fromstring(body)
                        dtext = root.findtext(
                            ".//{*}Days") or root.findtext(".//Days")
                        if dtext is not None:
                            days = float(dtext)
                            if days <= 0:
                                raise ValueError(dtext)
                    except _ET.ParseError:
                        raise S3Error("MalformedXML") from None
                    except ValueError as e:
                        raise S3Error("InvalidArgument",
                                      f"bad Days: {e}") from None
                from ..storage.errors import StorageError as _SE
                try:
                    restored = h.tier_mgr.restore_object(
                        bucket, key, query.get("versionId", [""])[0],
                        days=days)
                except _SE as e:
                    from .api_errors import from_storage_error as _fse
                    raise _fse(e) from None
                if not restored:
                    raise S3Error("InvalidObjectState")
                return Response(202)
            if "select" in query:
                return h.select_object_content(bucket, key, query, body,
                                               headers)
            if "uploads" in query:
                return h.create_multipart(bucket, key, headers)
            if "uploadId" in query:
                return h.complete_multipart(bucket, key, query, body)
            raise S3Error("MethodNotAllowed")
        raise S3Error("MethodNotAllowed")
