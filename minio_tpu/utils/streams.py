"""Streaming body plumbing: bounded readers for the O(batch) data path.

The role of the reference's reader stack (hash.Reader internal/hash/
reader.go:63, http chunked/aws-chunked decoding, GetObjectReader
cmd/object-api-utils.go:392-528): request bodies flow from the socket to
the erasure encoder in bounded chunks, with content hashes verified at
EOF instead of after buffering the whole object, and responses flow back
as an iterator of assembled ranges.
"""

from __future__ import annotations

import hashlib
import queue as _queue

from ..observe import span as ospan
from ..observe.metrics import DATA_PATH

#: Dedicated digest workers for PipelinedMD5.  They must NOT share an
#: engine pool: an md5 worker occupies its slot for a whole PUT, and a
#: worker that only ever drains its own queue can never deadlock — the
#: same isolation argument as ErasureSet._iter_pool.
_MD5_POOL = None


def _md5_pool():
    global _MD5_POOL
    if _MD5_POOL is None:
        from concurrent.futures import ThreadPoolExecutor
        _MD5_POOL = ThreadPoolExecutor(max_workers=4,
                                       thread_name_prefix="mtpu-md5")
    return _MD5_POOL


class PipelinedMD5:
    """MD5 streamed off the caller's thread so the S3 ETag digest
    overlaps encode+write instead of running serially before them.
    Same bytes in the same order, so the hex digest is byte-identical
    to hashlib.md5(body).

    Two engines behind one API:

      * native lanes (MTPU_NATIVE_DIGEST=1, default, when native/
        digest.cc builds): the stream registers with the shared
        multi-buffer lane scheduler (utils/digestlanes.py), so every
        concurrent ETag stream in the process advances together through
        SIMD lanes in one GIL-released call per tick — aggregate rate
        is lane-parallel on one core;
      * hashlib oracle (=0): the original dedicated-pool worker; the
        byte-exactness oracle the differential tests pin.

    update()/hexdigest() mirror hashlib's; close() is the abandon path
    (PUT failed before the etag was needed); on the oracle path a
    worker-side idle timeout backstops paths that miss close(), so an
    exception can never leak a pool slot."""

    _IDLE_TIMEOUT = 60.0

    def __init__(self):
        from . import digestlanes
        self._stream = None
        self._hex = None
        self._lent = False       # writable views are lent, not copied
        if digestlanes.use_native():
            self._sched = digestlanes.scheduler()
            self._stream = self._sched.open()
        else:
            self._q = _queue.SimpleQueue()
            self._closed = False
            self._fut = _md5_pool().submit(self._run)

    def _run(self) -> str:
        h = hashlib.md5()
        while True:
            try:
                piece = self._q.get(timeout=self._IDLE_TIMEOUT)
            except _queue.Empty:     # abandoned mid-stream
                return h.hexdigest()
            if piece is None:
                return h.hexdigest()
            h.update(piece)

    def update(self, piece) -> None:
        # Both digest engines hold queued pieces instead of consuming
        # them synchronously, and a writable view is VOLATILE (the
        # pooled PUT-ingest ring refills its buffers).  Once the ring
        # has borrowed this digest (`lend`) it is held as it is: the
        # ring waits for `wait_consumed` before it refills.  From
        # anyone else it is stabilized with one copy here, a fresh
        # buffer a piece (counted).  Immutable pieces (bytes, readonly
        # views from the bytes path) are never copied.
        if self._stream is not None:
            self._sched.update(self._stream, piece, lent=self._lent)
            return
        if isinstance(piece, memoryview) and not piece.readonly:
            piece = bytes(piece)
            DATA_PATH.record_put_fresh_buffer(len(piece))
        self._q.put(piece)

    def lend(self) -> bool:
        """The caller owns the writable views this digest will be fed
        and promises `wait_consumed(queued())` before it overwrites or
        frees what it has fed so far: `update` then holds them without
        a copy.  False where the engine cannot say when it is done with
        a piece (the hashlib oracle's queue): it goes on copying and
        there is nothing to wait for."""
        self._lent = self._stream is not None
        return self._lent

    def queued(self) -> int:
        """Bytes handed to `update` so far (the lanes' count)."""
        return self._stream.total

    def wait_consumed(self, upto: int) -> None:
        """Block until the first `upto` bytes handed to `update` are
        hashed (utils/digestlanes.py: `wait_consumed`)."""
        self._sched.wait_consumed(self._stream, upto)

    def feed(self, data, chunk_len: int = 1 << 20) -> None:
        """Queue an entire in-memory body as chunk-sized views (no
        copies) — the bytes-path shape: queue everything, then encode
        while the lanes/worker digest."""
        mv = memoryview(data)
        for off in range(0, len(mv), chunk_len):
            self.update(mv[off:off + chunk_len])

    def close(self) -> None:
        if self._stream is not None:
            # Finalize, don't abandon: callers use close() both as the
            # pre-hexdigest flush and as failure cleanup, and the lane
            # row is freed either way once the worker pads the stream.
            if self._hex is None:
                self._sched.finalize_async(self._stream)
            return
        if not self._closed:
            self._closed = True
            self._q.put(None)

    def hexdigest(self) -> str:
        if self._stream is not None:
            if self._hex is None:
                self._hex = self._sched.digest(self._stream).hex()
            return self._hex
        self.close()
        return self._fut.result()


class StreamError(IOError):
    """Malformed or truncated request body; maps to a 400-class S3
    error at the HTTP layer (IncompleteBody), not a 500."""


def is_reader(x) -> bool:
    """Anything with .read(n) that is not already bytes-like."""
    return (not isinstance(x, (bytes, bytearray, memoryview))
            and hasattr(x, "read"))


def ensure_bytes(x) -> bytes:
    """Drain a reader (compat path for non-streaming backends)."""
    if isinstance(x, (bytes, bytearray, memoryview)):
        return bytes(x)
    out = bytearray()
    while True:
        piece = x.read(1 << 20)
        if not piece:
            return bytes(out)
        out += piece


def _readinto_via_read(read, b) -> int:
    """readinto fallback for a source that only exposes read(): one
    bounded read copied into the caller's buffer.  May return fewer
    bytes than len(b); returns 0 only at EOF (matching the read()
    contract of every reader in this module)."""
    mv = b if isinstance(b, memoryview) else memoryview(b)
    piece = read(len(mv))
    n = len(piece)
    if n:
        mv[:n] = piece
    return n


class BytesReader:
    """bytes -> reader (tests, adapters)."""

    def __init__(self, data: bytes):
        self._mv = memoryview(data)
        self._pos = 0

    def read(self, n: int = -1) -> bytes:
        if n is None or n < 0:
            n = len(self._mv) - self._pos
        out = self._mv[self._pos:self._pos + n]
        self._pos += len(out)
        return bytes(out)

    def readinto(self, b) -> int:
        mv = b if isinstance(b, memoryview) else memoryview(b)
        n = min(len(mv), len(self._mv) - self._pos)
        if n:
            mv[:n] = self._mv[self._pos:self._pos + n]
            self._pos += n
        return n


def _note_pull(path: str, recvs: int) -> None:
    """Count one pull of a request body and say on the `http.read_body`
    span it ran under (where there is one) how it ran."""
    DATA_PATH.record_body_pull(path, recvs)
    cur = ospan.current()
    if cur is not None and cur.name == "http.read_body":
        cur.tag(path=path, recvs=cur.tags.get("recvs", 0) + recvs)


class LimitedReader:
    """Reads exactly `limit` bytes from `raw` (a connection's rfile)
    then reports EOF; a short source raises StreamError (truncated
    body).  One readinto()/read() that wants bytes is one *pull*: it
    fills what was asked for, short only at the source's end, and is
    counted (mtpu_body_pulls_total, mtpu_body_pull_recvs_total) under
    `path`."""

    path = "buffered"

    def __init__(self, raw, limit: int):
        self._raw = raw
        self._left = limit

    def _pull(self, mv) -> tuple[int, int]:
        """Fill `mv` from the source: (bytes filled, recvs made)."""
        # BufferedReader.readinto's own loop, run here so that the
        # recvs can be counted: readinto1 makes at most one a call (the
        # first of a body may be served from rfile's buffer instead).
        filled = recvs = 0
        while filled < len(mv):
            n = self._raw.readinto1(mv[filled:])
            if not n:
                break
            filled += n
            recvs += 1
        return filled, recvs

    def read(self, n: int = -1) -> bytes:
        if self._left <= 0:
            return b""
        if n is None or n < 0:
            n = self._left
        buf = bytearray(min(n, self._left))
        return bytes(memoryview(buf)[:self.readinto(buf)])

    def readinto(self, b) -> int:
        if self._left <= 0:
            return 0
        mv = b if isinstance(b, memoryview) else memoryview(b)
        want = min(len(mv), self._left)
        if not want:
            return 0
        n, recvs = self._pull(mv[:want])
        _note_pull(self.path, recvs)
        if not n:
            raise StreamError(f"body truncated ({self._left} bytes short)")
        self._left -= n
        return n


class SocketBodyReader(LimitedReader):
    """LimitedReader over a plain TCP connection whose pulls leave
    Python once: what rfile already holds (the header parse may have
    read the body's first bytes) is handed out first, then the caller's
    view is filled from the socket's descriptor by one
    `recv_exact(fd, view, timeout_ms)` (native/ecio_native.py): poll +
    recv until the view is full, the GIL released for all of it, where
    rfile gives it away and asks for it again twice a recv.  Never past
    `limit`, so a keep-alive connection's next request stays where it
    is.  The socket's own timeout is the idle limit of every wait."""

    path = "native"

    def __init__(self, rfile, limit: int, sock, recv_exact):
        super().__init__(rfile, limit)
        self._fd = sock.fileno()
        timeout = sock.gettimeout()
        self._timeout_ms = -1 if timeout is None else int(timeout * 1000)
        self._recv_exact = recv_exact
        self._held = True        # rfile may hold bytes of this body

    def _pull(self, mv) -> tuple[int, int]:
        filled = 0
        if self._held:
            # peek() returns all that rfile holds without moving (after
            # one recv of its own where it held nothing); readinto() of
            # no more than that is a copy out of its buffer.
            held = len(self._raw.peek())
            take = min(held, len(mv))
            if take:
                filled = self._raw.readinto(mv[:take])
            self._held = held > take
            if filled == len(mv) or not held:
                return filled, 0
        got, recvs = self._recv_exact(self._fd, mv[filled:],
                                      self._timeout_ms)
        return filled + got, recvs


def native_recv_exact():
    """`recv_exact` of the native library (native/ecio.cc), or None on
    a host whose toolchain cannot build it: bodies are then read
    through rfile."""
    from native import ecio_native
    from native._build import BuildError
    try:
        ecio_native.load()
    except BuildError:
        return None
    return ecio_native.recv_exact


class ExactLengthReader:
    """Pass-through reader that enforces the stream decodes to EXACTLY
    `want` bytes — a client-declared decoded length (aws-chunked
    x-amz-decoded-content-length) is only trustworthy for admission
    checks (quota, size caps) if something verifies it."""

    def __init__(self, src, want: int, exc=None):
        self._src = src
        self._want = want
        self._seen = 0
        self._exc = exc or (lambda msg: StreamError(msg))

    def read(self, n: int = -1) -> bytes:
        piece = self._src.read(n)
        self._seen += len(piece)
        if self._seen > self._want:
            raise self._exc(
                f"body longer than declared ({self._seen} > {self._want})")
        if not piece and self._seen != self._want:
            raise self._exc(
                f"body shorter than declared ({self._seen} < {self._want})")
        return piece

    def readinto(self, b) -> int:
        if not len(b):
            return 0
        ri = getattr(self._src, "readinto", None)
        n = (ri(b) if ri is not None
             else _readinto_via_read(self._src.read, b)) or 0
        self._seen += n
        if self._seen > self._want:
            raise self._exc(
                f"body longer than declared ({self._seen} > {self._want})")
        if not n and self._seen != self._want:
            raise self._exc(
                f"body shorter than declared ({self._seen} < {self._want})")
        return n


class MaxSizeReader:
    """Pass-through reader that raises `exc` once more than `cap` bytes
    have flowed — bounds bodies whose length is not declared up front
    (Transfer-Encoding: chunked)."""

    def __init__(self, src, cap: int, exc=None):
        self._src = src
        self._cap = cap
        self._seen = 0
        self._exc = exc or (lambda msg: StreamError(msg))

    def read(self, n: int = -1) -> bytes:
        piece = self._src.read(n)
        self._seen += len(piece)
        if self._seen > self._cap:
            raise self._exc(f"body exceeds {self._cap} bytes")
        return piece

    def readinto(self, b) -> int:
        if not len(b):
            return 0
        ri = getattr(self._src, "readinto", None)
        n = (ri(b) if ri is not None
             else _readinto_via_read(self._src.read, b)) or 0
        self._seen += n
        if self._seen > self._cap:
            raise self._exc(f"body exceeds {self._cap} bytes")
        return n


class HashVerifyReader:
    """Pass-through reader that verifies the stream's SHA-256 at EOF
    (the hash.Reader role, internal/hash/reader.go:63).  `on_mismatch`
    is the exception type raised."""

    def __init__(self, src, want_sha256_hex: str, exc=IOError):
        self._src = src
        self._want = want_sha256_hex
        self._h = hashlib.sha256()
        self._exc = exc
        self._done = False

    def read(self, n: int = -1) -> bytes:
        piece = self._src.read(n)
        if piece:
            self._h.update(piece)
        elif not self._done:
            self._done = True
            if self._h.hexdigest() != self._want:
                raise self._exc("content sha256 mismatch")
        return piece

    def readinto(self, b) -> int:
        if not len(b):
            return 0
        mv = b if isinstance(b, memoryview) else memoryview(b)
        ri = getattr(self._src, "readinto", None)
        n = (ri(mv) if ri is not None
             else _readinto_via_read(self._src.read, mv)) or 0
        if n:
            # hashlib consumes synchronously — safe on a pooled view.
            self._h.update(mv[:n])
        elif not self._done:
            self._done = True
            if self._h.hexdigest() != self._want:
                raise self._exc("content sha256 mismatch")
        return n


class HTTPChunkedReader:
    """Streaming decoder for HTTP/1.1 chunked transfer encoding (not
    aws-chunked — that is sigv4.StreamingBodyReader's job)."""

    def __init__(self, rfile):
        self._rf = rfile
        self._chunk_left = 0
        self._eof = False

    def _next_chunk(self) -> None:
        line = self._rf.readline().strip()
        try:
            self._chunk_left = int(line.split(b";")[0], 16)
        except ValueError:
            raise StreamError(f"bad chunk size line {line[:32]!r}") \
                from None
        if self._chunk_left == 0:
            # consume optional trailers up to the blank terminator line
            while True:
                line = self._rf.readline()
                if line in (b"\r\n", b"\n", b""):
                    break
            self._eof = True

    def read(self, n: int = -1) -> bytes:
        if self._eof:
            return b""
        out = bytearray()
        recvs = 0
        while n < 0 or len(out) < n:
            if self._chunk_left == 0:
                self._next_chunk()
                if self._eof:
                    break
            want = self._chunk_left if n < 0 \
                else min(self._chunk_left, n - len(out))
            piece = self._rf.read1(want)    # at most one recv: counted
            if not piece:
                raise StreamError("truncated chunked body")
            recvs += 1
            out += piece
            self._chunk_left -= len(piece)
            if self._chunk_left == 0:
                self._rf.read(2)         # chunk CRLF
        if recvs:
            _note_pull("buffered", recvs)
        return bytes(out)


#: Pooled PUT-ingest ring depth: a yielded view stays valid for
#: _RING_DEPTH - 1 further pulls.  The encode pipeline holds at most
#: one batch pending (chunk i is consumed while chunk i+1 is read), so
#: 2 would suffice; 4 leaves margin for a prefetching stage pipeline.
#: The ETag digest is no such consumer: what it was fed from a slot is
#: waited for before the slot is refilled, however deep the ring.
_RING_DEPTH = 4


def _fill_from(stream, view) -> int:
    """Fill writable memoryview `view` from `stream`; returns bytes
    filled (< len(view) only at EOF).  recv_into discipline: when the
    reader chain supports readinto, socket bytes land straight in the
    caller's buffer; otherwise read() pieces are copied in (still one
    destination buffer, no bytearray re-assembly)."""
    filled, total = 0, len(view)
    ri = getattr(stream, "readinto", None)
    if ri is not None:
        while filled < total:
            n = ri(view[filled:])
            if not n:
                break
            filled += n
        return filled
    while filled < total:
        piece = stream.read(total - filled)
        if not piece:
            break
        lp = len(piece)
        view[filled:filled + lp] = piece
        filled += lp
    return filled


def _pooled_chunks(head: bytes, stream, chunk_len: int, digest=None):
    """Streaming chunker over a ring of page-aligned buffer-pool leases
    (the PUT-ingest half of MTPU_ZEROCOPY): each chunk is filled in
    place via readinto instead of per-piece bytes allocs plus a final
    bytes() copy.  Yields writable memoryviews — valid until
    _RING_DEPTH - 1 further pulls.

    `digest` (a PipelinedMD5 the consumer feeds every chunk to before
    it pulls the next) may defer: the ring lends it the views, and
    refills a slot, or gives its leases back, only after the digest
    has consumed all it was fed while that slot's chunk was out.  Any
    other consumer that defers stabilizes the view with a copy of its
    own (PipelinedMD5 does, where nothing was lent)."""
    from ..ops import bpool
    pool = bpool.default_pool()
    slots: list = [None] * _RING_DEPTH
    lent = digest is not None and digest.lend()
    fed = [0] * _RING_DEPTH     # digest.queued() when slot's chunk came back
    try:
        carry = memoryview(head)
        i = 0
        while True:
            slot = i % _RING_DEPTH
            if slots[slot] is None:
                slots[slot] = pool.get(chunk_len)
            elif lent:
                digest.wait_consumed(fed[slot])
            view = memoryview(slots[slot].view)
            pre = min(len(carry), chunk_len)
            if pre:
                view[:pre] = carry[:pre]
                carry = carry[pre:]
            filled = pre
            if filled < chunk_len:
                # One span per chunk pulled (a pipeline batch), not one
                # per recv: the time blocked on the client's socket.
                with ospan.span("http.read_body"):
                    filled += _fill_from(stream, view[pre:])
            if filled < chunk_len:
                yield view[:filled], True    # final chunk (may be empty)
                return
            yield view, False
            if lent:
                fed[slot] = digest.queued()
            i += 1
    finally:
        if lent:
            # The lanes may still be hashing the last chunks: the
            # leases go back (to be overwritten by whoever leases them
            # next) only when nothing of this body is read any more.
            digest.wait_consumed(digest.queued())
        for lease in slots:
            if lease is not None:
                lease.release()


def batched_chunks(head: bytes, stream, chunk_len: int, digest=None):
    """Yield (chunk, is_last) with every chunk exactly chunk_len bytes
    except the final one (which may be empty when the total length is an
    exact multiple).  `head` is bytes already consumed from `stream`.
    `digest`: the PipelinedMD5 the consumer feeds each chunk to before
    it pulls the next one, where it has one (`_pooled_chunks`)."""
    if stream is None:
        # Pure-bytes source: zero-copy memoryview windows (the caller's
        # numpy frombuffer views them without materializing).
        mv = memoryview(head)
        pos = 0
        while len(mv) - pos > chunk_len:
            yield mv[pos:pos + chunk_len], False
            pos += chunk_len
        yield mv[pos:], True
        return
    from ..ops import zerocopy as _zc
    if _zc.zerocopy_enabled():
        yield from _pooled_chunks(head, stream, chunk_len, digest)
        return
    buf = bytearray(head)
    eof = False
    while True:
        with ospan.span("http.read_body"):
            while not eof and len(buf) < chunk_len:
                piece = stream.read(chunk_len - len(buf))
                if not piece:
                    eof = True
                else:
                    buf += piece
        if eof and len(buf) <= chunk_len:
            yield bytes(buf), True       # final chunk (may be empty)
            return
        yield bytes(buf[:chunk_len]), False
        del buf[:chunk_len]
