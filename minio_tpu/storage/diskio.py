"""Page-cache-bypass I/O (the internal/disk + O_DIRECT role).

The reference opens shard files O_DIRECT with aligned buffers and
fdatasync (cmd/xl-storage.go:1424,1533; internal/disk) so object bytes
don't double-buffer through the page cache — both for predictable
memory behavior and so benchmarks measure drives, not cache.

Modes (env MTPU_ODIRECT, a config knob like the reference's
MINIO_DRIVE_SYNC):
  - "fadvise" (default): buffered I/O + POSIX_FADV_DONTNEED after bulk
    transfers — portable cache-bypass-after-the-fact.
  - "direct": O_DIRECT aligned reads for bulk data (page-aligned scratch
    via mmap), fadvise on writes; falls back to buffered when alignment
    or the filesystem refuses.
  - "off": plain buffered I/O (tests that assert on page-cache warmth).
"""

from __future__ import annotations

import functools
import mmap
import os

ALIGN = 4096
BULK = 128 * 1024          # below this, cache behavior is irrelevant


def mode() -> str:
    m = os.environ.get("MTPU_ODIRECT", "fadvise")
    return m if m in ("off", "fadvise", "direct") else "fadvise"


def drops_after_read(length: int) -> bool:
    """The buffered read's cache policy: a bulk read of `length` bytes
    drops the file's pages once done, unless the mode is "off"."""
    return mode() != "off" and length >= BULK


@functools.cache
def native_read_rows():
    """`read_rows` of the native library (native/ecio.cc: a segment's
    shard rows in one call), or None on a host whose toolchain cannot
    build it."""
    from native import ecio_native
    from native._build import BuildError
    try:
        ecio_native.load()
    except BuildError:
        return None
    return ecio_native.read_rows


def osync() -> bool:
    """Synchronous durability (fsync/fdatasync on the write path).

    Default OFF, matching the reference: MinIO only fsyncs when
    MINIO_FS_OSYNC is set (cf. globalFSOSync, cmd/globals.go) —
    durability otherwise comes from writing the stripe to a quorum of
    independent drives, and a torn write on one drive is caught by
    bitrot verification and healed from parity. Per-append fdatasync
    costs ~1-3 ms x drives x batches and dominated PUT latency."""
    return os.environ.get("MTPU_OSYNC", "off") == "on"


def drop_cache(fd: int) -> None:
    """Advise the kernel to evict this file's pages (post-I/O)."""
    try:
        os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
    except (AttributeError, OSError):
        pass


def read_range(path: str, offset: int, length: int) -> bytes:
    """Read [offset, offset+length) (length < 0 = to EOF) honoring the
    configured cache mode.  Raises FileNotFoundError/IsADirectoryError
    like open()."""
    m = mode()
    if length < 0:
        length = max(os.path.getsize(path) - offset, 0)
    if m == "direct" and length >= BULK:
        data = _direct_read(path, offset, length)
        if data is not None:
            return data
    with open(path, "rb") as f:
        if offset:
            f.seek(offset)
        data = f.read(length)
        if drops_after_read(length):
            drop_cache(f.fileno())
        return data


def _direct_read(path: str, offset: int, length: int) -> bytes | None:
    """O_DIRECT read with page-aligned scratch; None -> caller falls
    back to buffered (unsupported fs, EINVAL, ...)."""
    if not hasattr(os, "O_DIRECT"):
        return None
    a_off = offset & ~(ALIGN - 1)
    a_end = (offset + length + ALIGN - 1) & ~(ALIGN - 1)
    need = a_end - a_off
    try:
        fd = os.open(path, os.O_RDONLY | os.O_DIRECT)
    except OSError:
        return None
    try:
        # Page-aligned scratch leased from the recycling pool
        # (ops/bpool.py) instead of a fresh anonymous mmap per call —
        # the pool's own fallback IS that mmap when it's full or off.
        from ..ops import bpool
        with bpool.default_pool().get(need) as buf:
            view = memoryview(buf)
            os.lseek(fd, a_off, os.SEEK_SET)
            got = 0
            while got < need:
                with view[got:] as window:
                    n = os.readv(fd, [window])
                if n <= 0:
                    break              # EOF (file shorter than aligned end)
                got += n
            lo = offset - a_off
            hi = min(lo + length, got)
            return b"" if hi <= lo else bytes(view[lo:hi])
    except OSError:
        return None
    finally:
        os.close(fd)


def read_range_view(path: str, offset: int, length: int) -> memoryview:
    """Zero-copy read: mmap the byte range and return a memoryview over
    the page cache (the map stays alive through the view).  The host
    fast path hands these straight to the fused native verify kernel —
    shard bytes then cross the kernel boundary zero times.

    Shard files are immutable once published (append-only staging, then
    rename), so the SIGBUS-on-truncate hazard of reading mmaps doesn't
    arise on this path; the range is clamped against the inode size at
    map time, so a short file yields a short view exactly like a short
    read() — callers verify the expected framed length themselves.
    """
    if length == 0:
        return memoryview(b"")
    fd = os.open(path, os.O_RDONLY)
    try:
        size = os.fstat(fd).st_size
        if length < 0 or offset + length > size:
            # read() semantics: a range past EOF returns what exists
            # (callers size-check the framed layout themselves).
            length = max(size - offset, 0)
        if length == 0:
            return memoryview(b"")
        a_off = offset & ~(ALIGN - 1)
        mm = mmap.mmap(fd, length + (offset - a_off), mmap.MAP_PRIVATE,
                       mmap.PROT_READ, offset=a_off)
        return memoryview(mm)[offset - a_off:offset - a_off + length]
    finally:
        os.close(fd)


def write_done(fd: int, nbytes: int) -> bool:
    """Post-write cache policy for bulk shard writes (the write side of
    the O_DIRECT role: staged shard bytes should not linger in cache).

    Dirty pages can't be evicted, so sync first — fdatasync per batch
    also spreads the publish-time fsync cost across the stream, like
    the reference's O_DIRECT+fdatasync writer (cmd/xl-storage.go:1533).
    Returns True when the durability policy is satisfied (callers then
    skip their own fsync) — which includes osync()=off, where no sync
    is wanted at all."""
    if not osync():
        return True
    if mode() != "off" and nbytes >= BULK:
        try:
            os.fdatasync(fd)
        except OSError:
            return False
        drop_cache(fd)
        return True
    return False
