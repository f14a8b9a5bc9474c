"""The yardstick's own S3 client: SigV4 from the public spec, one kept-alive
connection, stdlib only.

A copy of what `minio_tpu/server/client.py` + `sigv4.sign_request` do on the
client's side, kept here so that a later PR to the program cannot change the
client that measures it.  Imports nothing of the program.
"""

from __future__ import annotations

import datetime
import hashlib
import hmac
import http.client
import time
import urllib.parse
import xml.etree.ElementTree as ET

ALGORITHM = "AWS4-HMAC-SHA256"
UNSIGNED_PAYLOAD = "UNSIGNED-PAYLOAD"
OK = (200, 204, 206)


class S3Error(Exception):
    def __init__(self, status: int, code: str, message: str):
        self.status, self.code, self.message = status, code, message
        super().__init__(f"{status} {code}: {message}")


def _hmac(key: bytes, msg: str) -> bytes:
    return hmac.new(key, msg.encode(), hashlib.sha256).digest()


def _uri_encode(s: str, encode_slash: bool = True) -> str:
    return urllib.parse.quote(s, safe="-._~" if encode_slash else "-._~/")


def sign(access: str, secret: str, region: str, method: str, path: str,
         query: dict[str, str], headers: dict[str, str],
         payload_hash: str) -> dict[str, str]:
    """The headers SigV4 adds (Authorization, x-amz-date,
    x-amz-content-sha256) for a request with `headers` (Host among them)."""
    now = datetime.datetime.now(datetime.timezone.utc)
    amz_date = now.strftime("%Y%m%dT%H%M%SZ")
    date = amz_date[:8]
    h = {k.lower(): v for k, v in headers.items()}
    h["x-amz-date"] = amz_date
    h["x-amz-content-sha256"] = payload_hash
    signed = sorted(h)
    canon_query = "&".join(f"{_uri_encode(k)}={_uri_encode(query[k])}"
                           for k in sorted(query))
    canon = "\n".join([
        method, _uri_encode(path, encode_slash=False) or "/", canon_query,
        "".join(f"{k}:{' '.join(h[k].split())}\n" for k in signed),
        ";".join(signed), payload_hash])
    scope = f"{date}/{region}/s3/aws4_request"
    sts = "\n".join([ALGORITHM, amz_date, scope,
                     hashlib.sha256(canon.encode()).hexdigest()])
    key = _hmac(_hmac(_hmac(_hmac(f"AWS4{secret}".encode(), date), region),
                      "s3"), "aws4_request")
    sig = hmac.new(key, sts.encode(), hashlib.sha256).hexdigest()
    return {"Authorization": f"{ALGORITHM} Credential={access}/{scope}, "
                             f"SignedHeaders={';'.join(signed)}, "
                             f"Signature={sig}",
            "x-amz-date": amz_date, "x-amz-content-sha256": payload_hash}


class S3Client:
    """One client = one HTTP/1.1 connection, reopened when the server
    closed it.  `request` returns (status, headers, body) and raises only
    on transport errors; the named operations raise S3Error on a non-2xx.

    `attempts` is how often a request is sent before a connection that the
    server closed or reset counts as a failure.  Set-up resends (3); from
    the barrier on the harness sets it to 1, so a request that the server
    dropped inside the window or the checks is a failed request."""

    def __init__(self, host: str, port: int, access: str = "minioadmin",
                 secret: str = "minioadmin", region: str = "us-east-1",
                 timeout: float = 300.0):
        self.host, self.port = host, port
        self.access, self.secret, self.region = access, secret, region
        self.timeout = timeout
        self.attempts = 3
        self.reconnects = 0
        self._conn: http.client.HTTPConnection | None = None

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def request(self, method: str, path: str,
                query: dict[str, str] | None = None, body=b"",
                headers: dict[str, str] | None = None,
                unsigned_len: int | None = None, into=None):
        """`body` is bytes, signed by its SHA-256; or, with `unsigned_len`
        set, an iterable of byte chunks of that total length sent as
        UNSIGNED-PAYLOAD (never hashed or joined on this side).  `into` is
        a writable buffer of the caller's: a 200's body that fits is read
        into it and returned as a view of it, so a client that reads 64 MiB
        objects in a loop maps no fresh 64 MiB for each."""
        query = dict(query or {})
        headers = dict(headers or {})
        headers["Host"] = f"{self.host}:{self.port}"
        if unsigned_len is None:
            payload_hash = hashlib.sha256(body).hexdigest()
        else:
            payload_hash = UNSIGNED_PAYLOAD
            headers["Content-Length"] = str(unsigned_len)
        headers.update(sign(self.access, self.secret, self.region, method,
                            path, query, headers, payload_hash))
        url = urllib.parse.quote(path, safe="/~-._")
        if query:
            url += "?" + urllib.parse.urlencode(query)
        for attempt in range(self.attempts):
            if self._conn is None:
                self._conn = http.client.HTTPConnection(
                    self.host, self.port, timeout=self.timeout)
            try:
                self._conn.request(method, url, body=body or None,
                                   headers=headers)
                resp = self._conn.getresponse()
                size = resp.length
                if (into is None or resp.status != 200 or size is None
                        or size > len(into)):
                    data = resp.read()
                else:
                    data, got = memoryview(into)[:size], 0
                    while got < size:
                        n = resp.readinto(data[got:])
                        if not n:
                            raise http.client.IncompleteRead(
                                bytes(data[:got]), size - got)
                        got += n
            except (http.client.RemoteDisconnected, BrokenPipeError,
                    ConnectionResetError):
                # The server closed or reset the connection before it
                # answered (the set-up's connect burst can pass its listen
                # backlog of 5): open a new one and send again, as S3 SDKs
                # do.  `reconnects` says how often it happened.
                self.close()
                if attempt == self.attempts - 1:
                    raise
                self.reconnects += 1
                time.sleep(0.05 * (attempt + 1))
                continue
            if resp.will_close:
                self.close()
            return resp.status, dict(resp.getheaders()), data
        raise AssertionError("unreachable")

    def _ok(self, status: int, headers: dict, data: bytes):
        if status in OK:
            return headers, data
        code, msg = "Unknown", ""
        try:
            root = ET.fromstring(data)
            code = root.findtext("Code", "Unknown")
            msg = root.findtext("Message", "")
        except ET.ParseError:
            pass
        raise S3Error(status, code, msg)

    # -- the operations the traffic uses ---------------------------------------

    def make_bucket(self, bucket: str) -> None:
        self._ok(*self.request("PUT", f"/{bucket}"))

    def put_object(self, bucket: str, key: str, chunks, size: int,
                   query: dict[str, str] | None = None,
                   headers: dict[str, str] | None = None) -> str:
        """PUT (or UploadPart, by `query`) of `chunks`; the ETag."""
        h, _ = self._ok(*self.request("PUT", f"/{bucket}/{key}", query=query,
                                      body=chunks, headers=headers,
                                      unsigned_len=size))
        return h.get("ETag", "").strip('"')

    def get_object(self, bucket: str, key: str, into=None):
        """The object's bytes; a view of `into` where it holds them."""
        return self._ok(*self.request("GET", f"/{bucket}/{key}",
                                      into=into))[1]

    def head_object(self, bucket: str, key: str) -> dict:
        status, h, _ = self.request("HEAD", f"/{bucket}/{key}")
        if status != 200:
            raise S3Error(status, "HeadFailed", "")
        return h

    def delete_object(self, bucket: str, key: str) -> None:
        self._ok(*self.request("DELETE", f"/{bucket}/{key}"))

    def create_multipart(self, bucket: str, key: str,
                         headers: dict[str, str] | None = None) -> str:
        _, data = self._ok(*self.request("POST", f"/{bucket}/{key}",
                                         query={"uploads": ""},
                                         headers=headers))
        return next(e.text for e in ET.fromstring(data).iter()
                    if e.tag.endswith("UploadId"))

    def complete_multipart(self, bucket: str, key: str, upload_id: str,
                           parts: list[tuple[int, str]]) -> None:
        inner = "".join(f"<Part><PartNumber>{n}</PartNumber>"
                        f"<ETag>\"{e}\"</ETag></Part>" for n, e in parts)
        body = (f"<CompleteMultipartUpload>{inner}"
                f"</CompleteMultipartUpload>").encode()
        _, data = self._ok(*self.request("POST", f"/{bucket}/{key}",
                                         query={"uploadId": upload_id},
                                         body=body))
        # S3 answers 200 and may still carry an <Error> in the body.
        if b"<Error>" in data:
            raise S3Error(200, "CompleteFailed", data[:200].decode("replace"))
