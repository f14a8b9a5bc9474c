"""The front door's body read, over real loopback sockets.

A streamed PUT / UploadPart body with a Content-Length on a plain TCP
connection is pulled from the socket by one native call a pull
(utils/streams.SocketBodyReader over native/ecio.cc:ec_recv_exact); TLS,
chunked transfer encoding and a host without the library read through
rfile (streams.LimitedReader / HTTPChunkedReader).  Every case here sends
real bytes to a served S3Server and holds the two paths to the same
bytes, ETags, errors and connection discipline; the counts
(mtpu_body_pulls_total{path}) say which path ran.
"""

import datetime
import errno
import hashlib
import http.client
import socket
import threading
import time

import numpy as np
import pytest

from minio_tpu.engine.erasure_set import BATCH_BLOCKS, BLOCK_SIZE
from minio_tpu.engine.pools import ServerPools
from minio_tpu.engine.sets import ErasureSets
from minio_tpu.observe import span as ospan
from minio_tpu.observe.metrics import DATA_PATH
from minio_tpu.ops import bpool
from minio_tpu.server import sigv4
from minio_tpu.server.client import S3Client
from minio_tpu.server.server import S3Server
from minio_tpu.server.sigv4 import Credentials
from minio_tpu.storage.drive import LocalDrive
from minio_tpu.utils import streams
from native import ecio_native
from native._build import BuildError

ACCESS, SECRET = "sockadmin", "sockadmin-secret-key"
BUCKET = "sock"
KIB, MIB = 1 << 10, 1 << 20
PATHS = ("native", "buffered")


def body_of(size: int, seed: int = 0) -> bytes:
    return np.random.default_rng([size, seed]).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def pulls() -> dict:
    snap = DATA_PATH.snapshot()
    return {"pulls": dict(snap["body_pulls"]),
            "recvs": dict(snap["body_pull_recvs"])}


def grown(before: dict, kind: str = "pulls") -> dict:
    now = pulls()[kind]
    return {p: now[p] - before[kind][p] for p in PATHS}


def make_server(root, native: bool, **kw) -> S3Server:
    drives = [LocalDrive(str(root / f"d{i}")) for i in range(4)]
    pools = ServerPools([ErasureSets(drives, set_drive_count=4)])
    srv = S3Server(pools, Credentials(ACCESS, SECRET), **kw)
    if not native:
        srv._recv_exact = None           # what a host without g++ boots with
    else:
        assert srv._recv_exact is not None, "native/ecio.cc did not build"
    return srv.start()


@pytest.fixture(scope="module")
def servers(tmp_path_factory):
    """One server a path, same drives layout: {path: (server, client)}."""
    out = {}
    for path in PATHS:
        srv = make_server(tmp_path_factory.mktemp(path), path == "native")
        cli = S3Client(srv.endpoint, ACCESS, SECRET)
        cli.make_bucket(BUCKET)
        out[path] = (srv, cli)
    yield out
    for srv, _ in out.values():
        srv.shutdown()


def signed_put(cli, path: str, body: bytes, declared, query=None,
               extra=None) -> tuple[dict, bytes]:
    """(headers, wire body) of a PUT whose x-amz-content-sha256 is
    `declared`: "UNSIGNED-PAYLOAD", the bytes the hash is taken of, or
    STREAMING_PAYLOAD (the body is then aws-chunked here)."""
    now = datetime.datetime.now(datetime.timezone.utc)
    headers = {"Host": f"{cli.host}:{cli.port}", **(extra or {})}
    q = {k: [v] for k, v in (query or {}).items()}
    auth = sigv4.sign_request(cli.creds, "PUT", path, q, headers, declared,
                              now=now)
    headers.update(auth)
    if declared == sigv4.STREAMING_PAYLOAD:
        amz_date = now.strftime("%Y%m%dT%H%M%SZ")
        scope = f"{amz_date[:8]}/{cli.creds.region}/s3/aws4_request"
        body = sigv4.encode_streaming_body(
            cli.creds, scope, amz_date,
            auth["Authorization"].rsplit("Signature=", 1)[1], body,
            chunk_size=256 * KIB)
    headers["Content-Length"] = str(len(body))
    return headers, body


def put(cli, key: str, body: bytes, declared="UNSIGNED-PAYLOAD",
        query=None) -> tuple[int, dict, bytes]:
    path = f"/{BUCKET}/{key}"
    headers, wire = signed_put(cli, path, body, declared, query)
    url = path + ("?" + "&".join(f"{k}={v}" for k, v in query.items())
                  if query else "")
    conn = http.client.HTTPConnection(cli.host, cli.port, timeout=120)
    try:
        conn.request("PUT", url, body=wire, headers=headers)
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def request_bytes(cli, key: str, body: bytes) -> bytes:
    """One whole unsigned-payload PUT as it goes on the wire."""
    path = f"/{BUCKET}/{key}"
    headers, wire = signed_put(cli, path, body, "UNSIGNED-PAYLOAD")
    head = f"PUT {path} HTTP/1.1\r\n" + "".join(
        f"{k}: {v}\r\n" for k, v in headers.items()) + "\r\n"
    return head.encode() + wire


def read_response(fp) -> tuple[int, dict, bytes]:
    """One HTTP/1.1 response with a Content-Length off a socket file."""
    status = fp.readline()
    assert status, "connection closed before a response"
    headers = {}
    while True:
        line = fp.readline()
        if line in (b"\r\n", b""):
            break
        k, _, v = line.decode().partition(":")
        headers[k.strip().lower()] = v.strip()
    body = fp.read(int(headers.get("content-length", 0)))
    return int(status.split()[1]), headers, body


# -- the same bytes on both paths ---------------------------------------------

@pytest.mark.parametrize("size", [
    0, 1, 8 * KIB - 1, 8 * KIB + 1, MIB, 32 * MIB - 1, 32 * MIB + 1, 64 * MIB,
], ids=["0", "1", "8k-1", "8k+1", "1m", "32m-1", "32m+1", "64m"])
def test_body_is_the_clients_bytes_on_both_paths(servers, size):
    """Sizes across rfile's 8 KiB buffer and the engine's 32 MiB pull."""
    src = body_of(size)
    etags = {}
    for path, (_, cli) in servers.items():
        before = pulls()
        status, headers, out = put(cli, f"o{size}", src)
        assert status == 200, out
        etags[path] = headers["ETag"].strip('"')
        assert cli.get_object(BUCKET, f"o{size}") == src
        other = PATHS[1 - PATHS.index(path)]
        got = grown(before)
        assert got[other] == 0, (path, got)
        assert (got[path] > 0) == (size > 0), (path, got)
    assert etags["native"] == etags["buffered"] == hashlib.md5(
        src).hexdigest()


@pytest.mark.parametrize("mode", ["unsigned", "signed", "signed-bad-sha256",
                                  "aws-chunked"])
def test_readers_above_the_native_one(servers, mode):
    """HashVerifyReader, StreamingSigV4Reader and ExactLengthReader sit
    on the native bottom reader unchanged."""
    _, cli = servers["native"]
    src = body_of(2 * MIB + 33, seed=len(mode))
    declared = {"unsigned": "UNSIGNED-PAYLOAD", "signed": src,
                "signed-bad-sha256": b"another body",
                "aws-chunked": sigv4.STREAMING_PAYLOAD}[mode]
    before = pulls()
    status, _, out = put(cli, f"mode-{mode}", src, declared)
    got = grown(before)
    assert got["native"] > 0 and got["buffered"] == 0, got
    if mode == "signed-bad-sha256":
        assert status == 400 and b"XAmzContentSHA256Mismatch" in out
        assert cli.request("GET", f"/{BUCKET}/mode-{mode}")[0] == 404
    else:
        assert status == 200, out
        assert cli.get_object(BUCKET, f"mode-{mode}") == src


# -- the connection after the body --------------------------------------------

@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("first", [100, 100 * KIB, 3 * MIB],
                         ids=["in-rfile", "across-rfile", "3m"])
def test_keepalive_next_request_is_not_eaten(servers, path, first):
    """Two PUTs sent back to back on one connection before either is
    answered: the first body's read stops at its Content-Length, so the
    second's request line is still there (in rfile's buffer where the
    first body is small, in the socket otherwise)."""
    _, cli = servers[path]
    a, b = body_of(first, seed=1), body_of(5000, seed=2)
    with socket.create_connection((cli.host, cli.port), timeout=60) as s:
        s.sendall(request_bytes(cli, f"ka-a{first}", a)
                  + request_bytes(cli, f"ka-b{first}", b))
        fp = s.makefile("rb")
        for src in (a, b):
            status, headers, out = read_response(fp)
            assert status == 200, out
            assert headers["etag"].strip('"') == hashlib.md5(src).hexdigest()
    assert cli.get_object(BUCKET, f"ka-a{first}") == a
    assert cli.get_object(BUCKET, f"ka-b{first}") == b


@pytest.mark.parametrize("path", PATHS)
def test_client_closes_mid_body(servers, path):
    """Half a body, then the client's side closes: IncompleteBody (the
    truncation, not a crash), the connection closed, nothing stored."""
    _, cli = servers[path]
    wire = request_bytes(cli, "cut", body_of(MIB))
    with socket.create_connection((cli.host, cli.port), timeout=60) as s:
        s.sendall(wire[:len(wire) - MIB // 2])
        s.shutdown(socket.SHUT_WR)
        fp = s.makefile("rb")
        status, _, out = read_response(fp)
        assert status == 400 and b"IncompleteBody" in out, out
        assert b"truncated" in out
        assert fp.read() == b""                  # the server closed it
    assert cli.request("GET", f"/{BUCKET}/cut")[0] == 404


@pytest.mark.parametrize("path", PATHS)
def test_stalled_client_gets_request_timeout(tmp_path, monkeypatch, path):
    """A client that stops sending mid-body for longer than the socket
    timeout: RequestTimeout, connection closed, the handler's thread
    back (nothing in flight)."""
    monkeypatch.setenv("MTPU_SOCKET_TIMEOUT", "0.5")
    srv = make_server(tmp_path, path == "native")
    try:
        cli = S3Client(srv.endpoint, ACCESS, SECRET)
        cli.make_bucket(BUCKET)
        wire = request_bytes(cli, "stall", body_of(MIB))
        before = pulls()
        with socket.create_connection((cli.host, cli.port),
                                      timeout=30) as s:
            s.sendall(wire[:len(wire) - MIB // 2])
            t0 = time.monotonic()
            fp = s.makefile("rb")
            status, _, out = read_response(fp)
            assert status == 400 and b"RequestTimeout" in out, out
            assert 0.4 <= time.monotonic() - t0 < 10
            assert fp.read() == b""
        other = PATHS[1 - PATHS.index(path)]
        assert grown(before)[other] == 0
        deadline = time.monotonic() + 5
        while srv._inflight and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv._inflight == 0
    finally:
        srv.shutdown()


# -- connections that keep today's reader ---------------------------------------

def _self_signed(tmp_path) -> tuple[str, str]:
    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import rsa
    from cryptography.x509.oid import NameOID
    key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
    name = x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, "127.0.0.1")])
    now = datetime.datetime.now(datetime.timezone.utc)
    cert = (x509.CertificateBuilder().subject_name(name).issuer_name(name)
            .public_key(key.public_key())
            .serial_number(x509.random_serial_number())
            .not_valid_before(now - datetime.timedelta(minutes=1))
            .not_valid_after(now + datetime.timedelta(days=1))
            .sign(key, hashes.SHA256()))
    cert_file, key_file = tmp_path / "public.crt", tmp_path / "private.key"
    cert_file.write_bytes(cert.public_bytes(serialization.Encoding.PEM))
    key_file.write_bytes(key.private_bytes(
        serialization.Encoding.PEM,
        serialization.PrivateFormat.TraditionalOpenSSL,
        serialization.NoEncryption()))
    return str(cert_file), str(key_file)


def test_tls_listener_reads_through_rfile(tmp_path):
    """An SSLSocket's record layer is in Python's hands: a server that
    has the library still reads a TLS body through rfile."""
    srv = make_server(tmp_path, True, certs=_self_signed(tmp_path))
    try:
        cli = S3Client(srv.endpoint, ACCESS, SECRET, verify_tls=False)
        cli.make_bucket(BUCKET)
        src = body_of(3 * MIB + 5)
        before = pulls()
        h = cli.put_object_stream(BUCKET, "tls", streams.BytesReader(src),
                                  len(src))
        got = grown(before)
        assert got["buffered"] > 0 and got["native"] == 0, got
        assert grown(before, "recvs")["buffered"] >= got["buffered"]
        assert h["ETag"].strip('"') == hashlib.md5(src).hexdigest()
        assert cli.get_object(BUCKET, "tls") == src
    finally:
        srv.shutdown()


def test_chunked_transfer_encoding_reads_through_rfile(servers):
    """HTTPChunkedReader needs readline: chunked TE keeps rfile on a
    plain connection of a server that has the library."""
    _, cli = servers["native"]
    path = f"/{BUCKET}/te"
    src = body_of(MIB + 77)
    headers = {"Host": f"{cli.host}:{cli.port}",
               "Transfer-Encoding": "chunked",
               "x-amz-content-sha256": "UNSIGNED-PAYLOAD"}
    headers.update(sigv4.sign_request(cli.creds, "PUT", path, {}, headers,
                                      "UNSIGNED-PAYLOAD"))
    before = pulls()
    conn = http.client.HTTPConnection(cli.host, cli.port, timeout=60)
    try:
        conn.putrequest("PUT", path, skip_host=True,
                        skip_accept_encoding=True)
        for k, v in headers.items():
            conn.putheader(k, v)
        conn.endheaders()
        for i in range(0, len(src), 300 * KIB):
            piece = src[i:i + 300 * KIB]
            conn.send(f"{len(piece):x}\r\n".encode() + piece + b"\r\n")
        conn.send(b"0\r\n\r\n")
        resp = conn.getresponse()
        out = resp.read()
    finally:
        conn.close()
    assert resp.status == 200, out
    got = grown(before)
    assert got["buffered"] > 0 and got["native"] == 0, got
    assert cli.get_object(BUCKET, "te") == src


# -- counts, not clocks ---------------------------------------------------------

def read_body_spans(rec: dict) -> list[dict]:
    out = []
    for child in rec.get("spans", []):
        if child["name"] == "http.read_body":
            out.append(child)
        out += read_body_spans(child)
    return out


def upload_one_pull(srv, key: str, src: bytes) -> tuple[str, dict, list]:
    """UploadPart of one full pull (32 MiB) through `srv`, traced:
    (ETag, growth of the pull counters, tagged http.read_body spans)."""
    cli = S3Client(srv.endpoint, ACCESS, SECRET)
    cli.make_bucket(BUCKET)
    upload_id = cli.create_multipart(BUCKET, key)
    ospan.TRACER.configure(ring=8, sample=1.0)
    try:
        before = pulls()
        status, headers, out = put(
            cli, key, src, query={"partNumber": "1", "uploadId": upload_id})
        assert status == 200, out
        got = {kind: grown(before, kind) for kind in ("pulls", "recvs")}
        # The root span closes after the response is on the wire.
        deadline = time.monotonic() + 5
        while not (recs := [r for r in ospan.TRACER.traces()
                            if r["name"] == "api.UploadPart"]):
            assert time.monotonic() < deadline, "no api.UploadPart trace"
            time.sleep(0.01)
        rec = recs[-1]
    finally:
        ospan.TRACER.configure(ring=0, sample=1.0)
        ospan.TRACER.reset()
    etag = headers["ETag"].strip('"')
    cli.complete_multipart(BUCKET, key, upload_id, [(1, etag)])
    assert cli.get_object(BUCKET, key) == src
    return etag, got, [s for s in read_body_spans(rec) if s.get("tags")]


def test_one_pull_is_one_native_call(tmp_path, monkeypatch):
    """A 32 MiB pull on the native path: one more under
    mtpu_body_pulls_total{path="native"}, one http.read_body span tagged
    path=native with recvs >= 1, one call of the native function.  With
    the library unloadable the same request counts under `buffered` and
    stores the same bytes."""
    src = body_of(BATCH_BLOCKS * BLOCK_SIZE, seed=34)
    assert len(src) == 32 * MIB

    srv = make_server(tmp_path / "n", True)
    calls = []
    real = srv._recv_exact

    def counted(fd, view, timeout_ms):
        calls.append(len(view))
        return real(fd, view, timeout_ms)

    srv._recv_exact = counted
    try:
        etag_native, got, tagged = upload_one_pull(srv, "part", src)
    finally:
        srv.shutdown()
    assert got["pulls"] == {"native": 1, "buffered": 0}
    # All but what rfile held: at most its 8 KiB buffer, and exactly
    # that where the header parse left it empty and peek() filled it.
    assert len(calls) == 1 and calls[0] >= 32 * MIB - 8 * KIB
    assert [s["tags"]["path"] for s in tagged] == ["native"]
    assert tagged[0]["tags"]["recvs"] == got["recvs"]["native"] >= 1

    drives = [LocalDrive(str(tmp_path / "b" / f"d{i}")) for i in range(4)]
    pools = ServerPools([ErasureSets(drives, set_drive_count=4)])
    with monkeypatch.context() as m:     # the front door boots without it
        m.setattr(ecio_native, "_lib", None)
        m.setattr(ecio_native, "_load_error",
                  BuildError("g++ failed: no toolchain (test)"))
        assert streams.native_recv_exact() is None
        srv = S3Server(pools, Credentials(ACCESS, SECRET)).start()
    try:
        assert srv._recv_exact is None
        etag_buffered, got, tagged = upload_one_pull(srv, "part", src)
    finally:
        srv.shutdown()
    assert got["pulls"] == {"native": 0, "buffered": 1}
    assert got["recvs"]["buffered"] >= 1 and got["recvs"]["native"] == 0
    assert [s["tags"]["path"] for s in tagged] == ["buffered"]
    assert etag_buffered == etag_native == hashlib.md5(src).hexdigest()


def test_ingest_ring_is_leased_from_the_arena(servers, monkeypatch):
    """Four streamed 64 MiB bodies at once (the PUT cell's traffic): every
    32 MiB lease of their rings comes out of the pool's arena at its
    default size, so a body is received into pages that are already there;
    none falls back to a fresh mapping, whose every page would fault."""
    pool = bpool.BufferPool(total_bytes=bpool.bpool_bytes())
    monkeypatch.setattr(bpool, "_POOL", pool)
    _, cli = servers["native"]
    src = body_of(64 * MIB, seed=4)
    results = []

    def one(i):
        results.append(put(cli, f"ring{i}", src)[0])

    threads = [threading.Thread(target=one, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert results == [200] * 4
    st = pool.stats()
    assert st["gets"] >= 8 and st["fallbacks"] == 0, st
    assert st["in_use_bytes"] == 0           # every lease went back


# -- the native call's own contract -------------------------------------------

def test_recv_exact_fills_the_view_and_no_more():
    a, b = socket.socketpair()
    with a, b:
        src = body_of(3 * MIB)
        sender = threading.Thread(target=a.sendall, args=(src + b"NEXT",))
        sender.start()
        buf = bytearray(3 * MIB)
        got, recvs = ecio_native.recv_exact(b.fileno(), buf, 5000)
        sender.join(10)
        assert (got, bytes(buf)) == (3 * MIB, src) and recvs >= 1
        assert b.recv(16) == b"NEXT"             # left in the socket


def test_recv_exact_is_short_only_at_the_peers_close():
    a, b = socket.socketpair()
    with b:
        a.sendall(b"x" * 1000)
        a.close()
        buf = bytearray(4096)
        assert ecio_native.recv_exact(b.fileno(), buf, 5000) == (1000, 1)
        assert ecio_native.recv_exact(b.fileno(), buf, 5000) == (0, 0)


def test_recv_exact_idle_limit_is_per_wait_not_per_call():
    """Bytes that keep coming inside the limit never time the call out,
    however long it takes; a silence longer than the limit does."""
    a, b = socket.socketpair()
    with a, b:
        def drip():
            for _ in range(6):
                time.sleep(0.1)
                a.sendall(b"y" * 10)

        t = threading.Thread(target=drip)
        t.start()
        buf = bytearray(60)
        t0 = time.monotonic()
        assert ecio_native.recv_exact(b.fileno(), buf, 300)[0] == 60
        assert time.monotonic() - t0 >= 0.5      # longer than the limit
        t.join(10)
        t0 = time.monotonic()
        with pytest.raises(TimeoutError) as ei:
            ecio_native.recv_exact(b.fileno(), buf, 200)
        assert ei.value.errno == errno.ETIMEDOUT
        assert 0.15 <= time.monotonic() - t0 < 5


def test_recv_exact_refuses_what_it_cannot_fill():
    a, b = socket.socketpair()
    with a, b:
        with pytest.raises(ValueError):
            ecio_native.recv_exact(b.fileno(), b"read-only", 10)
        assert ecio_native.recv_exact(b.fileno(), bytearray(0), 10) == (0, 0)
    with pytest.raises(OSError) as ei:           # no such descriptor
        ecio_native.recv_exact(1 << 20, bytearray(8), 10)
    assert ei.value.errno == errno.EBADF
