"""Local drive backend — the xlStorage equivalent.

One `LocalDrive` owns one directory tree and implements the per-drive
contract the engine fans out to (cf. StorageAPI,
/root/reference/cmd/storage-interface.go:27, and xlStorage,
/root/reference/cmd/xl-storage.go:90):

- volumes (buckets) are top-level directories,
- an object is a directory holding ``xl.meta`` plus one subdirectory per
  version data-dir containing bitrot-framed shard files (``part.N``),
- writes land in a per-drive tmp area and are published atomically by
  renaming the whole data-dir + rewriting xl.meta (RenameData,
  /root/reference/cmd/xl-storage.go:1830),
- deletes first rename into the tmp trash area so visibility is atomic
  (moveToTrash, /root/reference/cmd/xl-storage.go:838).

Python file I/O here plays the role of the reference's O_DIRECT+fdatasync
Go paths; durability is fsync-on-publish.
"""

from __future__ import annotations

import errno
import os
import shutil
import threading
import uuid
import zlib

from . import bitrot_io, diskio, oscounters
from ..observe import span as ospan
from ..utils import msgpackx
from ..utils.crashpoints import crash_point
from .errors import (ErrDiskNotFound, ErrFileAccessDenied, ErrFileCorrupt,
                     ErrFileNotFound, ErrFileVersionNotFound, ErrIsNotRegular,
                     ErrPathNotFound, ErrVolumeExists, ErrVolumeNotEmpty,
                     ErrVolumeNotFound)
from .xlmeta import FileInfo, XLMeta

# Reserved system namespace on every drive (reference: .minio.sys).
SYS_VOL = ".mtpu.sys"
TMP_DIR = "tmp"
META_JOURNAL_DIR = "metajournal"
MULTIPART_DIR = "multipart"
BUCKET_META_DIR = "buckets"
XL_META_FILE = "xl.meta"
FORMAT_FILE = "format.json"

# Objects <= this are stored inline in xl.meta (cf. smallFileThreshold,
# /root/reference/cmd/xl-storage.go:59).
SMALL_FILE_THRESHOLD = 128 * 1024


def _is_valid_volname(vol: str) -> bool:
    return bool(vol) and "/" not in vol and vol not in (".", "..")


def _ensure_parent(p: str) -> None:
    """makedirs(dirname(p)) with the common cases first: one mkdir
    syscall when the grandparent exists, none when the parent does —
    os.makedirs stat-walks every ancestor on EVERY call, which adds up
    on the per-drive hot path."""
    d = os.path.dirname(p)
    try:
        os.mkdir(d)
    except FileExistsError:
        pass
    except FileNotFoundError:
        os.makedirs(d, exist_ok=True)


#: read_all's read size; an xl.meta with its inline shard stays under it.
_READ_ALL_CHUNK = 256 << 10


class LocalDrive:
    """One local drive rooted at `root`."""

    def __init__(self, root: str, create: bool = True):
        self.root = os.path.abspath(root)
        if create:
            os.makedirs(self.root, exist_ok=True)
        elif not os.path.isdir(self.root):
            raise ErrDiskNotFound(root)
        for sub in (TMP_DIR, META_JOURNAL_DIR, MULTIPART_DIR,
                    BUCKET_META_DIR):
            os.makedirs(os.path.join(self.root, SYS_VOL, sub), exist_ok=True)
        self._meta_lock = threading.Lock()
        # Per-process monotonic group-commit segment sequence (names
        # stay sortable in publish order; pid+uuid keep pre-fork
        # workers from clashing on the shared drive dir).
        self._meta_seq = 0
        self.disk_id: str = ""
        self.endpoint = root
        # per-drive syscall stats; doubles as the per-drive I/O span
        # source inside traced requests (observe/span.py)
        self._osc = oscounters.Counters(
            drive=os.path.basename(self.root))
        # Positive volume-existence cache: every data-path call
        # re-stats the volume dir otherwise (~8 stats per PUT across a
        # stripe). Same-process deletes invalidate; a cross-process
        # delete surfaces as ENOENT on the file op itself.
        self._vols: set[str] = set()

    # -- path helpers --------------------------------------------------------

    def _vol_path(self, vol: str) -> str:
        # Volumes are single path components directly under the root.
        if not _is_valid_volname(vol):
            raise ErrVolumeNotFound(vol)
        return os.path.join(self.root, vol)

    def _file_path(self, vol: str, path: str) -> str:
        base = self._vol_path(vol)
        p = os.path.normpath(os.path.join(base, path))
        # Confine to the volume, not just the drive root — '..' must not
        # reach sibling volumes or the reserved system namespace.
        if not (p + os.sep).startswith(base + os.sep):
            raise ErrFileAccessDenied(f"{vol}/{path}")
        return p

    def _check_vol(self, vol: str) -> str:
        p = self._vol_path(vol)
        if vol in self._vols:
            return p
        with self._osc.timed("stat"):
            ok = os.path.isdir(p)
        if not ok:
            raise ErrVolumeNotFound(vol)
        self._vols.add(vol)
        return p

    # -- volume ops ----------------------------------------------------------

    def init_sys_volume(self) -> None:
        """Recreate the reserved system volume skeleton (tmp/multipart/
        bucket-meta dirs). A replaced/wiped drive loses it at runtime;
        format heal calls this before rewriting format.json
        (cf. makeFormatErasureMetaVolumes, cmd/format-erasure.go)."""
        for sub in (TMP_DIR, META_JOURNAL_DIR, MULTIPART_DIR,
                    BUCKET_META_DIR):
            os.makedirs(os.path.join(self.root, SYS_VOL, sub),
                        exist_ok=True)

    def make_volume(self, vol: str) -> None:
        p = self._vol_path(vol)
        with self._osc.timed("stat"):
            exists = os.path.isdir(p)
        if exists:
            raise ErrVolumeExists(vol)
        with self._osc.timed("mkdir"):
            os.makedirs(p)

    def list_volumes(self) -> list[str]:
        out = []
        with self._osc.timed("listdir"):
            names = sorted(os.listdir(self.root))
        for name in names:
            if name == SYS_VOL or name.startswith("."):
                continue
            if os.path.isdir(os.path.join(self.root, name)):
                out.append(name)
        return out

    def stat_volume(self, vol: str) -> dict:
        p = self._check_vol(vol)
        with self._osc.timed("stat"):
            st = os.stat(p)
        return {"name": vol, "created_ns": int(st.st_mtime_ns)}

    def delete_volume(self, vol: str, force: bool = False) -> None:
        p = self._check_vol(vol)
        self._vols.discard(vol)
        if force:
            self._move_to_trash(p)
            return
        try:
            os.rmdir(p)
        except OSError as e:
            if e.errno == errno.ENOTEMPTY:
                raise ErrVolumeNotEmpty(vol) from e
            raise

    # -- small-file ops (metadata, config) -----------------------------------

    def write_all(self, vol: str, path: str, data: bytes) -> None:
        """Atomic small-file write (tmp + rename + fsync)."""
        self._check_vol(vol)
        with self._osc.timed("write", "meta_write"):
            return self._write_all(vol, path, data)

    def _write_all(self, vol: str, path: str, data: bytes) -> None:
        p = self._file_path(vol, path)
        self._ensure_parent_in_vol(vol, p)
        tmp = os.path.join(self.root, SYS_VOL, TMP_DIR,
                           f"wa-{uuid.uuid4().hex}")
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            crash_point("tmp.write.pre_fsync")
            os.fsync(f.fileno())
        crash_point("tmp.write.post_fsync")
        with self._osc.timed("rename"):
            os.replace(tmp, p)

    def read_all(self, vol: str, path: str) -> bytes:
        with self._osc.timed('read'):
            return self._read_all_impl(vol, path)

    def _read_all_impl(self, vol: str, path: str) -> bytes:
        p = self._file_path(vol, path)
        try:
            # os.open, os.read until empty, os.close: four system
            # calls for a file under the chunk, where open().read()
            # makes about twice as many (fstat, isatty's ioctl, lseek,
            # a second fstat).  Each is a place where a request thread
            # gives the GIL away and queues for it again: ~0.7 ms each
            # with 8 clients on the chip host (PERF.md §6, PR 30).
            fd = os.open(p, os.O_RDONLY)
            try:
                chunks = []
                while buf := os.read(fd, _READ_ALL_CHUNK):
                    chunks.append(buf)
            finally:
                os.close(fd)
            return b"".join(chunks)     # one chunk: that object itself
        except FileNotFoundError:
            raise ErrFileNotFound(f"{vol}/{path}") from None
        except IsADirectoryError:
            raise ErrFileNotFound(f"{vol}/{path}") from None
        except PermissionError:
            raise ErrFileAccessDenied(f"{vol}/{path}") from None

    def delete(self, vol: str, path: str, recursive: bool = False) -> None:
        with self._osc.timed('delete'):
            return self._delete_impl(vol, path, recursive)

    def _delete_impl(self, vol: str, path: str, recursive: bool = False) -> None:
        p = self._file_path(vol, path)
        if not os.path.exists(p):
            raise ErrFileNotFound(f"{vol}/{path}")
        if os.path.isdir(p):
            if recursive:
                self._move_to_trash(p)
            else:
                try:
                    os.rmdir(p)
                except OSError as e:
                    raise ErrFileAccessDenied(str(e)) from e
        else:
            os.remove(p)

    # -- shard-file ops ------------------------------------------------------

    def create_file(self, vol: str, path: str, data: bytes) -> None:
        with self._osc.timed('write', 'create'):
            return self._create_file_impl(vol, path, data)

    def _create_file_impl(self, vol: str, path: str, data: bytes) -> None:
        """Write a (bitrot-framed) shard file; parents auto-created.

        The engine stages shard files under the tmp volume and publishes
        them via rename_data — so this write itself needs no tmp hop.
        """
        self._check_vol(vol)
        p = self._file_path(vol, path)
        self._ensure_parent_in_vol(vol, p)
        with open(p, "wb") as f:
            f.write(data)
            f.flush()
            crash_point("shard.create.pre_fsync")
            # write_done syncs (fdatasync) before dropping cache; only
            # fsync ourselves when it didn't run (small/off-mode writes)
            if not diskio.write_done(f.fileno(), len(data)):
                os.fsync(f.fileno())
        crash_point("shard.create.post_fsync")

    def append_file(self, vol: str, path: str, data: bytes) -> None:
        with self._osc.timed('write', 'append'):
            return self._append_file_impl(vol, path, data)

    def _ensure_parent_in_vol(self, vol: str, p: str) -> None:
        """_ensure_parent that cannot resurrect a deleted volume: when
        the parent chain is missing, re-validate the volume with the
        cache bypassed so a cross-process bucket delete surfaces as
        ErrVolumeNotFound instead of silently recreating the dir."""
        d = os.path.dirname(p)
        try:
            with self._osc.timed("mkdir"):
                os.mkdir(d)
        except FileExistsError:
            pass
        except FileNotFoundError:
            self._vols.discard(vol)
            self._check_vol(vol)
            with self._osc.timed("mkdir"):
                os.makedirs(d, exist_ok=True)

    def _append_file_impl(self, vol: str, path: str, data) -> None:
        """Append to a staged shard file (streaming writes land batch by
        batch; rename_data fsyncs staged files before publishing).

        `data` is any contiguous buffer (bytes or a uint8 ndarray view
        of the fused-encode arena); a whole-buffer write bypasses the
        BufferedWriter copy path."""
        self._check_vol(vol)
        p = self._file_path(vol, path)
        self._ensure_parent_in_vol(vol, p)
        with open(p, "ab") as f:
            f.write(data)
            f.flush()
            diskio.write_done(f.fileno(), len(data))
        crash_point("shard.append")

    def write_file_batches(self, vol: str, path: str, batches) -> None:
        """Vectored staged-shard append: every batch in `batches` lands
        at EOF through ONE open + fallocate + pwritev sequence instead
        of an open/write/close round per batch (the CreateFile
        streaming-contract role, cmd/xl-storage.go:90 — our staging
        files are append-published, so "create" is append-at-EOF).

        With MTPU_ODIRECT=direct and a page-aligned (offset, total)
        the write goes O_DIRECT; EINVAL (tmpfs, odd fs) falls back to
        the buffered fd transparently.  Byte-identical to the
        append_file loop — pinned by the zerocopy matrix tests."""
        with self._osc.timed('write', 'append'):
            return self._write_file_batches_impl(vol, path, batches)

    def _write_file_batches_impl(self, vol: str, path: str,
                                 batches) -> None:
        self._check_vol(vol)
        p = self._file_path(vol, path)
        self._ensure_parent_in_vol(vol, p)
        total = sum(len(b) for b in batches)
        fd = os.open(p, os.O_WRONLY | os.O_CREAT, 0o644)
        try:
            pos = os.fstat(fd).st_size
            if total and diskio.mode() == "direct":
                # Preallocate ONLY in O_DIRECT mode: unbuffered writes
                # skip the page cache, so reserving the extent up
                # front avoids mid-stream ENOSPC and fragmentation.
                # Under buffered IO fallocate is a net LOSS on ext4 —
                # every write then pays unwritten-extent conversion
                # (~+50% per 1 MiB batch, measured) for a file that is
                # written once, renamed, and never extended again.
                try:
                    os.posix_fallocate(fd, pos, total)
                except (AttributeError, OSError):
                    pass             # preallocation is best-effort
            wfd = fd
            direct = -1
            if (diskio.mode() == "direct" and hasattr(os, "O_DIRECT")
                    and total >= diskio.BULK
                    and pos % diskio.ALIGN == 0
                    and total % diskio.ALIGN == 0
                    and all(len(b) % diskio.ALIGN == 0
                            for b in batches)):
                try:
                    direct = os.open(p, os.O_WRONLY | os.O_DIRECT)
                    wfd = direct
                except OSError:
                    direct = -1      # fs refuses O_DIRECT: buffered
            try:
                iov = [memoryview(b).cast("B") for b in batches
                       if len(b)]
                off = pos
                while iov:
                    try:
                        n = os.pwritev(wfd, iov[:512], off)
                    except OSError as e:
                        if wfd == direct and e.errno == errno.EINVAL:
                            # Alignment looked right but the fs still
                            # refused (e.g. tmpfs): redo buffered.
                            wfd = fd
                            continue
                        raise
                    if n <= 0:
                        raise OSError(errno.EIO, "short pwritev")
                    off += n
                    while iov and n >= len(iov[0]):
                        n -= len(iov[0])
                        iov.pop(0)
                    if n:
                        iov[0] = iov[0][n:]
            finally:
                if direct >= 0:
                    os.close(direct)
            diskio.write_done(fd, total)
        finally:
            os.close(fd)
        from ..observe.metrics import DATA_PATH
        DATA_PATH.record_zerocopy_vectored_write(total)
        crash_point("shard.append")

    def read_file(self, vol: str, path: str, offset: int = 0,
                  length: int = -1) -> bytes:
        with self._osc.timed('read'):
            return self._read_file_impl(vol, path, offset, length)

    def _read_file_impl(self, vol: str, path: str, offset: int = 0,
                  length: int = -1) -> bytes:
        """Bulk shard reads honor the page-cache-bypass mode
        (storage/diskio.py — the odirect-read role,
        cmd/xl-storage.go:1424)."""
        p = self._file_path(vol, path)
        try:
            return diskio.read_range(p, offset, length)
        except FileNotFoundError:
            raise ErrFileNotFound(f"{vol}/{path}") from None
        except IsADirectoryError:
            raise ErrIsNotRegular(f"{vol}/{path}") from None

    def read_file_view(self, vol: str, path: str, offset: int = 0,
                       length: int = -1) -> memoryview:
        """Zero-copy bulk read (mmap over the page cache) for the host
        fused verify path; same error surface as read_file — including
        short views for ranges past EOF (callers size-check the framed
        layout, exactly as they do for short read()s)."""
        p = self._file_path(vol, path)
        try:
            with self._osc.timed('read'):
                return diskio.read_range_view(p, offset, length)
        except FileNotFoundError:
            raise ErrFileNotFound(f"{vol}/{path}") from None
        except IsADirectoryError:
            raise ErrIsNotRegular(f"{vol}/{path}") from None

    def open_read_fd(self, vol: str, path: str) -> int:
        """Open a shard file read-only and hand the CALLER the fd (the
        sendfile-plan path: one fd serves both the mmap verify pass and
        the kernel-space sends, so a racing delete only unlinks the
        name — the verified bytes stay reachable).  Caller closes."""
        p = self._file_path(vol, path)
        try:
            with self._osc.timed('read'):
                return os.open(p, os.O_RDONLY)
        except FileNotFoundError:
            raise ErrFileNotFound(f"{vol}/{path}") from None
        except IsADirectoryError:
            raise ErrIsNotRegular(f"{vol}/{path}") from None

    def rename_file(self, src_vol: str, src_path: str, dst_vol: str,
                    dst_path: str) -> None:
        """Atomic same-drive file move (parents auto-created)."""
        src = self._file_path(src_vol, src_path)
        dst = self._file_path(dst_vol, dst_path)
        with self._osc.timed("rename"):
            if not os.path.isfile(src):
                raise ErrFileNotFound(f"{src_vol}/{src_path}")
            self._ensure_parent_in_vol(dst_vol, dst)
            os.replace(src, dst)

    def list_raw(self, vol: str, path: str = "") -> list[str]:
        """All directory entries (files and dirs) under a path, unfiltered —
        used for internal bookkeeping dirs (multipart staging)."""
        self._check_vol(vol)
        p = self._file_path(vol, path) if path else self._vol_path(vol)
        try:
            with self._osc.timed("listdir"):
                return sorted(os.listdir(p))
        except FileNotFoundError:
            raise ErrPathNotFound(f"{vol}/{path}") from None
        except NotADirectoryError:
            raise ErrPathNotFound(f"{vol}/{path}") from None

    def file_size(self, vol: str, path: str) -> int:
        p = self._file_path(vol, path)
        try:
            with self._osc.timed("stat"):
                st = os.stat(p)
        except FileNotFoundError:
            raise ErrFileNotFound(f"{vol}/{path}") from None
        if not os.path.isfile(p):
            raise ErrIsNotRegular(f"{vol}/{path}")
        return st.st_size

    # -- versioned metadata ops ---------------------------------------------

    def _meta_path(self, vol: str, obj: str) -> str:
        return self._file_path(vol, os.path.join(obj, XL_META_FILE))

    def _read_xlmeta(self, vol: str, obj: str) -> XLMeta:
        try:
            buf = self.read_all(vol, os.path.join(obj, XL_META_FILE))
        except ErrFileNotFound:
            raise ErrFileNotFound(f"{vol}/{obj}") from None
        return XLMeta.from_bytes(buf)

    def _write_xlmeta(self, vol: str, obj: str, meta: XLMeta,
                      new: bool = False) -> None:
        if not meta.versions:
            # Last version gone: remove the whole object dir.
            obj_dir = self._file_path(vol, obj)
            self._move_to_trash(obj_dir)
            return
        if new:
            # First xl.meta for this object: no reader can hold it yet,
            # so skip the tmp+rename dance (one fs metadata op instead
            # of two on the PUT hot path). A torn write is caught by
            # the xl.meta integrity checksum and reads as missing,
            # which quorum + heal already handle.
            p = self._file_path(vol, os.path.join(obj, XL_META_FILE))
            self._ensure_parent_in_vol(vol, p)
            with self._osc.timed("write", "meta_write"), \
                    open(p, "wb") as f:
                f.write(meta.to_bytes())
            return
        self.write_all(vol, os.path.join(obj, XL_META_FILE), meta.to_bytes())

    def read_version(self, vol: str, obj: str, version_id: str = "",
                     read_data: bool = False) -> FileInfo:
        """ReadVersion (cf. /root/reference/cmd/xl-storage.go:1183):
        returns FileInfo; inline data always included when present.

        Falls back to the legacy xl.json (format v1) when no xl.meta
        exists — the migration read path, cmd/xl-storage-format-v1.go."""
        self._check_vol(vol)
        try:
            meta = self._read_xlmeta(vol, obj)
        except ErrFileNotFound:
            from . import xlmeta_v1
            try:
                raw = self.read_all(vol,
                                    os.path.join(obj, xlmeta_v1.XL_JSON))
            except ErrFileNotFound:
                raise ErrFileNotFound(f"{vol}/{obj}") from None
            fi = xlmeta_v1.parse_xl_json(raw, vol, obj)
            if version_id and fi.version_id != version_id:
                from .errors import ErrFileVersionNotFound
                raise ErrFileVersionNotFound(
                    f"{vol}/{obj}@{version_id}") from None
            return fi
        fi = meta.get(version_id, vol, obj)
        return fi

    def write_metadata(self, vol: str, obj: str, fi: FileInfo) -> None:
        """Add/replace one version in xl.meta (WriteMetadata).

        A corrupt existing xl.meta is unreadable everywhere (its versions
        are already lost on this drive) — start fresh so heal can REPLACE
        it with the quorum-elected metadata instead of failing forever.
        """
        self._check_vol(vol)
        with ospan.span("storage.write_metadata"), self._meta_lock:
            try:
                meta = self._read_xlmeta(vol, obj)
            except (ErrFileNotFound, ErrFileCorrupt):
                meta = XLMeta()
            crash_point("meta.update")
            meta.add_version(fi)
            self._write_xlmeta(vol, obj, meta)
        from ..observe.metrics import DATA_PATH
        DATA_PATH.record_meta_publish()

    # -- group-committed metadata (PR 19, ops/metalanes.py) ------------------

    def _journal_dir(self) -> str:
        return os.path.join(self.root, SYS_VOL, META_JOURNAL_DIR)

    def write_metadata_many(self, items: list) -> list:
        """Group-commit a batch of WriteMetadata ops: stage every
        item's next xl.meta blob, persist ALL of them in ONE fsynced
        journal segment, then publish each blob with a plain (unsynced)
        tmp+rename.  One fsync pays for the whole batch instead of one
        per object — the group-commit shape of the reference's
        format-v2 small-object war (cmd/xl-storage-format-v2.go).

        `items` is a list of ``(vol, obj, fi)``; the return value is a
        same-length list of ``exception | None`` (per-item outcome, so
        one poisoned item cannot fail its batch-mates).

        Durability contract (same ack rule as write_metadata, same
        process-crash model as `_write_xlmeta(new=True)`): no caller is
        acked before the journal segment is fsynced; a kill-9 before
        the fsync loses only unacked items (the torn/missing segment is
        discarded by CRC at replay), a kill-9 after it replays the
        segment at boot (`sweep_stale`) and republishes every blob —
        zero acked-write loss.  Same-key items within a batch chain
        onto each other's staged metadata so no version is lost;
        publish order + last-blob-wins replay keep the final xl.meta
        identical to sequential solo writes.
        """
        out: list = [None] * len(items)
        blobs: list = []  # (idx, vol, obj, blob bytes)
        with self._meta_lock:
            staged: dict = {}
            for i, (vol, obj, fi) in enumerate(items):
                try:
                    self._check_vol(vol)
                    key = (vol, obj)
                    meta = staged.get(key)
                    if meta is None:
                        try:
                            meta = self._read_xlmeta(vol, obj)
                        except (ErrFileNotFound, ErrFileCorrupt):
                            meta = XLMeta()
                    meta.add_version(fi)
                    staged[key] = meta
                    blobs.append((i, vol, obj, meta.to_bytes()))
                except Exception as e:  # noqa: BLE001 — per-item verdict
                    out[i] = e
            if not blobs:
                return out
            crash_point("meta.stage")
            # One journal segment, one fsync, covering every staged
            # blob.  CRC over the payload makes a torn segment (crash
            # mid-write) self-discarding at replay; a discarded segment
            # is safe because nothing past this point has been acked.
            payload = msgpackx.packb({
                "v": 1,
                "entries": [{"vol": vol, "obj": obj, "blob": blob}
                            for _, vol, obj, blob in blobs],
            })
            self._meta_seq += 1
            seg = os.path.join(
                self._journal_dir(),
                f"seg-{self._meta_seq:012d}-{os.getpid()}-"
                f"{uuid.uuid4().hex}")
            with self._osc.timed("write"), open(seg, "wb") as f:
                f.write(b"MJ01")
                f.write(zlib.crc32(payload).to_bytes(4, "big"))
                f.write(payload)
                f.flush()
                os.fsync(f.fileno())
            crash_point("meta.fsync")
            # Publish phase: per-blob rename into place, no fsync (the
            # journal already holds the durable copy until the segment
            # is retired below).
            for i, vol, obj, blob in blobs:
                try:
                    crash_point("meta.publish")
                    self._publish_meta_blob(vol, obj, blob)
                except Exception as e:  # noqa: BLE001 — per-item verdict
                    out[i] = e
            try:
                os.unlink(seg)
            except OSError:
                pass
        from ..observe.metrics import DATA_PATH
        DATA_PATH.record_meta_group_commit(len(blobs))
        return out

    def _publish_meta_blob(self, vol: str, obj: str, blob: bytes) -> None:
        p = self._meta_path(vol, obj)
        self._ensure_parent_in_vol(vol, p)
        tmp = os.path.join(self.root, SYS_VOL, TMP_DIR,
                           f"mj-{uuid.uuid4().hex}")
        with self._osc.timed("write"):
            with open(tmp, "wb") as f:
                f.write(blob)
        with self._osc.timed("rename"):
            os.replace(tmp, p)

    def replay_meta_journal(self) -> int:
        """Boot recovery: republish xl.meta blobs from group-commit
        segments a crash left behind.  Segments sort by name (per-boot
        seq + pid) so the last republished blob per key wins, matching
        the original publish order; torn/corrupt segments are discarded
        (they were never fsync-complete, so nothing in them was acked).
        Returns the number of entries republished."""
        jdir = self._journal_dir()
        try:
            segs = sorted(os.listdir(jdir))
        except FileNotFoundError:
            return 0
        replayed = 0
        with self._meta_lock:
            for name in segs:
                seg = os.path.join(jdir, name)
                entries = []
                try:
                    with open(seg, "rb") as f:
                        raw = f.read()
                    if raw[:4] == b"MJ01" and len(raw) >= 8:
                        want = int.from_bytes(raw[4:8], "big")
                        payload = raw[8:]
                        if zlib.crc32(payload) == want:
                            doc = msgpackx.unpackb(payload)
                            entries = doc.get("entries", [])
                except (OSError, msgpackx.MsgpackError,
                        ValueError, AttributeError):
                    entries = []
                for ent in entries:
                    try:
                        self._publish_meta_blob(
                            ent["vol"], ent["obj"], ent["blob"])
                        replayed += 1
                    except (OSError, KeyError, TypeError,
                            ErrVolumeNotFound, ErrFileAccessDenied):
                        # Vol vanished since the crash — the entry has
                        # nowhere to land; drop it with the segment.
                        pass
                try:
                    os.unlink(seg)
                except OSError:
                    pass
        return replayed

    def update_metadata(self, vol: str, obj: str, fi: FileInfo) -> None:
        with self._meta_lock:
            meta = self._read_xlmeta(vol, obj)
            meta.find_version(fi.version_id)  # must exist
            meta.add_version(fi)
            self._write_xlmeta(vol, obj, meta)

    def rename_data(self, src_vol: str, src_dir: str, fi: FileInfo,
                    dst_vol: str, dst_obj: str) -> None:
        """Atomic publish: move staged data-dir into place + add version
        to xl.meta (cf. RenameData, /root/reference/cmd/xl-storage.go:1830).

        src_dir is the staging dir whose *contents* are the part files;
        they are moved to <dst_obj>/<fi.data_dir>/.
        """
        with ospan.span("storage.rename_data"):
            self._rename_data(src_vol, src_dir, fi, dst_vol, dst_obj)

    def _rename_data(self, src_vol: str, src_dir: str, fi: FileInfo,
                     dst_vol: str, dst_obj: str) -> None:
        self._check_vol(dst_vol)
        with self._meta_lock:
            fresh = False
            try:
                meta = self._read_xlmeta(dst_vol, dst_obj)
            except ErrFileNotFound:
                meta, fresh = XLMeta(), True
            except ErrFileCorrupt:
                meta = XLMeta()  # heal path will rewrite; don't block PUT
            # Non-versioned overwrite of the null version: free old datadir.
            old_dd = ""
            if fi.version_id == "":
                try:
                    old_dd = meta.delete_version("")
                except ErrFileVersionNotFound:
                    pass
                # Heal republishes the SAME data_dir; freeing it would
                # delete the files just moved into place.
                if old_dd == fi.data_dir:
                    old_dd = ""
            if fi.uses_data_dir():
                src = self._file_path(src_vol, src_dir)
                if not os.path.isdir(src):
                    raise ErrFileNotFound(f"{src_vol}/{src_dir}")
                # Durability before visibility (osync mode only —
                # default matches the reference's no-fsync data path,
                # see diskio.osync): staged part files were written
                # with plain appends; flush them (and the dir entry)
                # before the rename makes the version readable.
                if diskio.osync():
                    for name in os.listdir(src):
                        fp = os.path.join(src, name)
                        if os.path.isfile(fp):
                            fd = os.open(fp, os.O_RDONLY)
                            try:
                                os.fsync(fd)
                            finally:
                                os.close(fd)
                    dfd = os.open(src, os.O_RDONLY)
                    try:
                        os.fsync(dfd)
                    finally:
                        os.close(dfd)
                dst = self._file_path(dst_vol,
                                      os.path.join(dst_obj, fi.data_dir))
                self._ensure_parent_in_vol(dst_vol, dst)
                if os.path.isdir(dst):
                    self._move_to_trash(dst)
                with self._osc.timed("rename"):
                    os.replace(src, dst)
            crash_point("rename.pre_meta")
            meta.add_version(fi)
            self._write_xlmeta(dst_vol, dst_obj, meta, new=fresh)
            if old_dd:
                self._remove_data_dir(dst_vol, dst_obj, old_dd)

    def delete_version(self, vol: str, obj: str, version_id: str = "",
                       mark_delete: bool = False,
                       fi: FileInfo | None = None) -> None:
        """Remove one version (or write a delete marker when mark_delete).

        cf. DeleteVersion, /root/reference/cmd/xl-storage.go and the
        xlMetaV2 state machine (xl-storage-format-v2.go:1132).
        """
        self._check_vol(vol)
        with self._meta_lock:
            meta = self._read_xlmeta(vol, obj)
            if mark_delete:
                assert fi is not None and fi.deleted
                meta.add_version(fi)
                self._write_xlmeta(vol, obj, meta)
                return
            dd = meta.delete_version(version_id)
            self._write_xlmeta(vol, obj, meta)
            if dd:
                self._remove_data_dir(vol, obj, dd)
            if not meta.versions:
                self._cleanup_empty_parents(vol, obj)

    def _remove_data_dir(self, vol: str, obj: str, data_dir: str) -> None:
        p = self._file_path(vol, os.path.join(obj, data_dir))
        if os.path.isdir(p):
            self._move_to_trash(p)

    def _cleanup_empty_parents(self, vol: str, obj: str) -> None:
        """Remove now-empty parent dirs up to the volume root."""
        base = self._check_vol(vol)
        p = os.path.dirname(self._file_path(vol, obj))
        while p.startswith(base + os.sep):
            try:
                os.rmdir(p)
            except OSError:
                break
            p = os.path.dirname(p)

    # -- listing / walking ---------------------------------------------------

    def list_dir(self, vol: str, path: str = "") -> list[str]:
        """Entries directly under a prefix dir; directories get a trailing
        slash. Object dirs (containing xl.meta) count as file entries."""
        self._check_vol(vol)
        p = self._file_path(vol, path) if path else self._vol_path(vol)
        try:
            with self._osc.timed("listdir"):
                names = sorted(os.listdir(p))
        except FileNotFoundError:
            raise ErrPathNotFound(f"{vol}/{path}") from None
        except NotADirectoryError:
            raise ErrPathNotFound(f"{vol}/{path}") from None
        out = []
        for name in names:
            full = os.path.join(p, name)
            if os.path.isdir(full):
                if os.path.isfile(os.path.join(full, XL_META_FILE)):
                    out.append(name)
                else:
                    out.append(name + "/")
            elif name == XL_META_FILE:
                continue
        return out

    def walk_dir(self, vol: str, prefix: str = ""):
        """Yield (object_name, xl.meta bytes) depth-first in lexical order
        (cf. WalkDir, /root/reference/cmd/metacache-walk.go:60)."""
        base = self._check_vol(vol)
        start = self._file_path(vol, prefix) if prefix else base
        # The prefix may be a partial name: walk its parent and filter.
        walk_root = start if os.path.isdir(start) else os.path.dirname(start)
        if not os.path.isdir(walk_root):
            return
        for dirpath, dirnames, filenames in os.walk(walk_root):
            dirnames.sort()
            if XL_META_FILE in filenames:
                rel = os.path.relpath(dirpath, base).replace(os.sep, "/")
                if rel.startswith(prefix) or not prefix:
                    try:
                        with open(os.path.join(dirpath, XL_META_FILE),
                                  "rb") as f:
                            yield rel, f.read()
                    except OSError:
                        pass
                dirnames[:] = []  # don't descend into data dirs

    def walk_page(self, vol: str, prefix: str = "", after: str = "",
                  limit: int = 1000):
        """One bounded page of the lexical walk: up to `limit`
        (object_name, xl.meta bytes) entries with name > `after`,
        plus an eof flag. Subtrees that cannot contain names past
        `after` are pruned, so paging a huge bucket never re-reads
        what earlier pages covered (the WalkDir + resume-marker role,
        cf. cmd/metacache-walk.go:60 with WalkDirOptions.ForwardTo)."""
        base = self._check_vol(vol)
        start = self._file_path(vol, prefix) if prefix else base
        walk_root = start if os.path.isdir(start) \
            else os.path.dirname(start)
        out: list[tuple[str, bytes]] = []

        def emit(dirpath: str, rel: str) -> bool:
            if (not prefix or rel.startswith(prefix)) and rel > after:
                if len(out) >= limit:
                    return False
                try:
                    with open(os.path.join(dirpath, XL_META_FILE),
                              "rb") as f:
                        out.append((rel, f.read()))
                except OSError:
                    pass
            return True

        def descend(dirpath: str) -> bool:
            """-> False when the page filled mid-subtree (not eof)."""
            try:
                names = os.listdir(dirpath)
            except OSError:
                return True
            # Global lexical order: an object dir d emits exactly "d";
            # a container dir d emits names starting "d/". Siblings
            # must therefore be visited in (name if object else
            # name+"/") order — plain name order would emit "x/..."
            # before sibling "x!a" even though '!' < '/'.
            items = []
            for name in names:
                sub = os.path.join(dirpath, name)
                if not os.path.isdir(sub):
                    continue
                is_obj = os.path.isfile(os.path.join(sub, XL_META_FILE))
                items.append((name if is_obj else name + "/", name,
                              is_obj, sub))
            items.sort()
            for key, name, is_obj, sub in items:
                rel = os.path.relpath(sub, base).replace(os.sep, "/")
                if is_obj:
                    if not emit(sub, rel):
                        return False
                    continue         # object dir: don't enter data dirs
                # Prune: every name under rel starts with rel+"/";
                # skip when that whole range sorts <= after.
                if after and rel + "/" < after[:len(rel) + 1]:
                    continue
                if len(out) >= limit:
                    return False
                if not descend(sub):
                    return False
            return True

        if not os.path.isdir(walk_root):
            return [], True
        if os.path.isfile(os.path.join(walk_root, XL_META_FILE)):
            # the prefix IS an object
            rel = os.path.relpath(walk_root, base).replace(os.sep, "/")
            return ([], True) if not emit(walk_root, rel) else (out, True)
        # descend() checks the limit before every append/recursion, so
        # out never exceeds it.
        return out, descend(walk_root)

    # -- bitrot verify -------------------------------------------------------

    def verify_file(self, vol: str, path: str, shard_size: int,
                    expected_logical: int | None = None,
                    algo: str = bitrot_io.DEFAULT_ALGO) -> None:
        """Full-file bitrot verification (cf. VerifyFile,
        /root/reference/cmd/xl-storage.go:2194). Raises ErrFileCorrupt.

        Under MTPU_ZEROCOPY the sweep is vectored and bounded: whole
        frame batches land in ONE preadv syscall each, into recycled
        bpool scratch — memory stays O(batch) where the old whole-file
        read() allocated O(file) per verified shard.  =0 keeps the
        whole-file oracle."""
        from ..ops import zerocopy as zc
        if not zc.zerocopy_enabled():
            data = self.read_file(vol, path)
            if expected_logical is not None:
                want = bitrot_io.bitrot_shard_file_size(
                    expected_logical, shard_size, algo)
                if len(data) != want:
                    raise ErrFileCorrupt(
                        f"size mismatch: {len(data)} != {want}")
            bitrot_io.unframe_shard(data, shard_size, verify=True,
                                    algo=algo)
            return
        from ..ops import bpool
        p = self._file_path(vol, path)
        frame = bitrot_io.digest_size(algo) + shard_size
        batch = max(1, (4 << 20) // frame) * frame
        try:
            fd = os.open(p, os.O_RDONLY)
        except FileNotFoundError:
            raise ErrFileNotFound(f"{vol}/{path}") from None
        except IsADirectoryError:
            raise ErrIsNotRegular(f"{vol}/{path}") from None
        try:
            size = os.fstat(fd).st_size
            if expected_logical is not None:
                want = bitrot_io.bitrot_shard_file_size(
                    expected_logical, shard_size, algo)
                if size != want:
                    raise ErrFileCorrupt(
                        f"size mismatch: {size} != {want}")
            pool = bpool.default_pool()
            off = 0
            while off < size:
                # Whole frames per batch; the trailing partial frame
                # (the tail shard) rides in the final batch and
                # verifies through unframe_shard's tail path.
                n = min(size - off, batch)
                if size - (off + n) < frame:
                    n = size - off
                with self._osc.timed('read'), pool.get(n) as buf:
                    got = 0
                    mv = memoryview(buf)
                    while got < n:
                        r = os.preadv(fd, [mv[got:]], off + got)
                        if r <= 0:
                            raise ErrFileCorrupt(
                                f"short read at {off + got}")
                        got += r
                    bitrot_io.unframe_shard(buf[:n], shard_size,
                                            verify=True, algo=algo)
                off += n
        finally:
            os.close(fd)

    # -- disk info / format --------------------------------------------------

    def disk_info(self) -> dict:
        st = os.statvfs(self.root)
        return {
            "total": st.f_blocks * st.f_frsize,
            "free": st.f_bavail * st.f_frsize,
            "used": (st.f_blocks - st.f_bfree) * st.f_frsize,
            "endpoint": self.endpoint,
            "id": self.disk_id,
            "online": True,
            # process-wide per-syscall-class counters/timings
            # (cmd/os-instrumented.go role)
            "os": self._osc.snapshot(),
        }

    def get_disk_id(self) -> str:
        return self.disk_id

    # -- internals -----------------------------------------------------------

    def _move_to_trash(self, path: str) -> None:
        """Atomic disappearance: rename into tmp trash, then remove."""
        trash = os.path.join(self.root, SYS_VOL, TMP_DIR,
                             f"trash-{uuid.uuid4().hex}")
        try:
            with self._osc.timed("rename"):
                os.replace(path, trash)
        except FileNotFoundError:
            return
        shutil.rmtree(trash, ignore_errors=True)

    def clear_tmp(self) -> None:
        tmp = os.path.join(self.root, SYS_VOL, TMP_DIR)
        for name in os.listdir(tmp):
            shutil.rmtree(os.path.join(tmp, name), ignore_errors=True)

    def sweep_stale(self) -> dict:
        """Boot-time recovery sweep (formatErasureCleanupTmpLocalEndpoints
        role, cmd/prepare-storage.go): everything under tmp belongs to a
        dead boot epoch — staged writes that never published, trash that
        never finished deleting.  The whole tmp dir is renamed aside (one
        atomic op, so a concurrent boot can't race the file walk), a
        fresh one is created, and the aside tree is deleted.  Orphaned
        multipart ``stage-*`` files (a part upload killed between encode
        and rename) are swept too; parked part files and upload metadata
        stay — the upload itself is still resumable.

        Returns counts for the recovery metrics.
        """
        counts = {"tmp_entries": 0, "mp_stage": 0, "meta_journal": 0}
        # Replay fsynced group-commit metadata segments FIRST — they
        # carry acked writes whose xl.meta publish a crash cut short,
        # and nothing below (tmp/multipart sweep) may run ahead of
        # re-establishing them.
        counts["meta_journal"] = self.replay_meta_journal()
        if counts["meta_journal"]:
            from ..observe.metrics import DATA_PATH
            DATA_PATH.record_meta_journal_replay(counts["meta_journal"])
        tmp = os.path.join(self.root, SYS_VOL, TMP_DIR)
        try:
            stale = os.listdir(tmp)
        except FileNotFoundError:
            stale = []
        if stale:
            counts["tmp_entries"] = len(stale)
            aside = os.path.join(self.root, SYS_VOL,
                                 f"{TMP_DIR}-old-{uuid.uuid4().hex}")
            try:
                os.replace(tmp, aside)
            except OSError:
                aside = tmp  # fall back to in-place removal
            os.makedirs(tmp, exist_ok=True)
            shutil.rmtree(aside, ignore_errors=True)
        else:
            os.makedirs(tmp, exist_ok=True)
        mp = os.path.join(self.root, SYS_VOL, MULTIPART_DIR)
        for dirpath, _dirnames, filenames in os.walk(mp):
            for name in filenames:
                if name.startswith("stage-"):
                    try:
                        os.remove(os.path.join(dirpath, name))
                        counts["mp_stage"] += 1
                    except OSError:
                        pass
        return counts

    def __repr__(self) -> str:
        return f"LocalDrive({self.root!r})"


# -- a segment's shard rows in one native call ----------------------------------


def rows_readable(drives) -> bool:
    """Whether `read_rows` may read these drives: each is a LocalDrive of
    this process, health-wrapped or not, and not a subclass that
    programs its own reads (storage/naughty.py: the native call would go
    around them); the cache mode is not O_DIRECT, whose aligned reads
    are diskio.read_range's alone; and the native library built."""
    return (all(d.__class__ is LocalDrive for d in drives)
            and diskio.mode() != "direct"
            and diskio.native_read_rows() is not None)


def read_rows(drives: list, vol: str, path: str, offset: int, length: int,
              k: int, out, exact_end: bool) -> list:
    """Bytes [offset, offset + length) of `vol`/`path` on `drives`, one
    drive after another in that order, each into the next free
    `length`-byte slot of the writable buffer `out`, until k have
    answered in full: a GET segment's shard rows in ONE native call,
    the GIL released once, under one span `storage.read_rows` (tags
    `rows`, `failed`, `bytes`).  `exact_end`: a file must end where the
    range does.  The page-cache policy is `read_file`'s.

    Each drive tried is counted as its `read_file` call would be: a
    `read` in its OS counters and, where it is health-wrapped, a
    `read_file` call in the wrapper's stats and breaker
    (HealthWrappedDrive.note_call).  A file shorter than the range (or
    longer, with `exact_end`) answered that call and fails here, as a
    short segment fails the engine's parse.

    Returns per drive (slot, error, seconds): (slot, None, s) for a row
    read into `out`'s slot; (None, error, s) for a failure; (None, None,
    0.0) for a drive not tried, k rows being in before its turn."""
    try:
        paths = [d._file_path(vol, path) for d in drives]
    except (ErrVolumeNotFound, ErrFileAccessDenied) as e:
        return [(None, e, 0.0)] * len(drives)
    from native.ecio_native import ROW_SIZE, ROW_UNTRIED
    with ospan.span("storage.read_rows") as sp:
        err, slot, ns = diskio.native_read_rows()(
            paths, offset, length, k, out, exact_end,
            diskio.drops_after_read(length))
        res = []
        failed = 0
        for d, e, j, t in zip(drives, err.tolist(), slot.tolist(),
                              (ns / 1e9).tolist()):
            if e == ROW_UNTRIED:
                res.append((None, None, 0.0))
                continue
            d._osc.add("read", t)
            call_err = row_err = None
            if e == ROW_SIZE:
                row_err = ErrFileCorrupt(
                    f"{vol}/{path}: not {length} bytes at {offset}")
            elif e == errno.ENOENT:
                call_err = ErrFileNotFound(f"{vol}/{path}")
            elif e == errno.EISDIR:
                call_err = ErrIsNotRegular(f"{vol}/{path}")
            elif e:
                call_err = OSError(e, os.strerror(e))
            note = getattr(d, "note_call", None)    # the health wrapper
            if note is not None:
                note("read_file", t * 1e3, call_err)
            row_err = row_err or call_err
            failed += row_err is not None
            res.append((None if row_err else j, row_err, t))
        rows = int((slot >= 0).sum())
        sp.tag(rows=rows, failed=failed, bytes=rows * length)
    return res
