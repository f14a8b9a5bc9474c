"""`serve.py` with the timed path broken underneath: for the tests only.

    python benchmark/tests/faulty_serve.py <control dir> <trace> <argv...>

The fault is named by BENCH_FAULT in the environment (set by the test; no
benchmark run reads it).  Each one patches the program, in the serving
process, at a point that every backend's path goes through:

  controls (break a guarantee the configuration states)
    lose_shard   an acknowledged PUT is on K+M-1 drives: the first drive
                 publishes, then loses its part files
    bad_digest   a bitrot frame that does not verify: one bit of the digest
                 of the first data shard's first frame is flipped as written
  faults (an answer altered where it is produced)
    flip_parity  one bit of the first parity shard of every encoded batch
    flip_get     one bit of every GET body
    flip_rebuilt one bit of the first row a read's decode rebuilds, on the
                 device's program (`ShardMath.verify_transform`) and in the
                 fused host kernel (`ecio_native.get_verify`) alike: only a
                 read that finds a data shard missing returns it
    keep_deleted a DELETE is acknowledged and the object stays
"""

from __future__ import annotations

import glob
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import serve  # noqa: E402

DIGEST = 32


def _flipped(buf, at: int) -> bytes:
    out = bytearray(buf)
    out[at] ^= 1
    return bytes(out)


def install(fault: str) -> None:
    from minio_tpu.engine.erasure_set import ErasureSet
    from minio_tpu.engine.pools import ServerPools
    from minio_tpu.storage.drive import LocalDrive

    if fault in ("flip_parity", "bad_digest"):
        orig_encode = ErasureSet._encode_chunks

        def encode(self, chunks, k, m, *args, **kwargs):
            shard, at = (k, DIGEST + 7) if fault == "flip_parity" else (0, 3)
            for framed in orig_encode(self, chunks, k, m, *args, **kwargs):
                framed = list(framed)
                if len(framed[shard]) > at:     # the server's own tiny
                    framed[shard] = _flipped(framed[shard], at)  # objects
                yield framed
        ErasureSet._encode_chunks = encode
    elif fault == "flip_get":
        orig_get = ServerPools.get_object_iter

        def get_object_iter(self, *args, **kwargs):
            fi, body = orig_get(self, *args, **kwargs)

            def altered():
                done = False
                for chunk in body:
                    if not done and len(chunk):
                        chunk, done = _flipped(chunk, len(chunk) // 2), True
                    yield chunk
            return fi, altered()
        ServerPools.get_object_iter = get_object_iter
    elif fault == "flip_rebuilt":
        import numpy as np

        from minio_tpu.engine.shardmath import ShardMath
        from native import ecio_native
        orig_vt, orig_gv = ShardMath.verify_transform, ecio_native.get_verify

        def verify_transform(self, x, k, m, sources, targets, algo):
            digests, out = orig_vt(self, x, k, m, sources, targets, algo)
            if out is not None:
                out = np.array(out)
                out[0, 0, 0] ^= 1
            return digests, out

        def get_verify(frames, sel, nb, S, k, m, targets, out=None):
            y, ok, nbad = orig_gv(frames, sel, nb, S, k, m, targets, out=out)
            if targets and not nbad:
                y[0, targets[0], 0] ^= 1
            return y, ok, nbad
        ShardMath.verify_transform = verify_transform
        ecio_native.get_verify = get_verify
    elif fault == "keep_deleted":
        ServerPools.delete_object = lambda self, *args, **kwargs: None
    elif fault == "lose_shard":
        orig_rename = LocalDrive.rename_data

        def rename_data(self, src_vol, src_dir, fi, dst_vol, dst_obj):
            orig_rename(self, src_vol, src_dir, fi, dst_vol, dst_obj)
            if self.root.rstrip("/").endswith("/d1") and fi.data_dir:
                for part in glob.glob(os.path.join(
                        self.root, dst_vol, dst_obj, fi.data_dir, "part.*")):
                    os.unlink(part)
        LocalDrive.rename_data = rename_data
    else:
        raise SystemExit(f"faulty_serve: unknown fault {fault!r}")


if __name__ == "__main__":
    install(os.environ["BENCH_FAULT"])
    sys.exit(serve.main())
