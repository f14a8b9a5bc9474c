"""The least work the chip must do for the users' bytes, and the least time
it can take: the roofline under `encode_roofline` and `decode_roofline`.

The work is what D bytes of acknowledged PUT data need at K data + M parity
shards, whatever implements it.  Padding, staging copies and extra launches
are not work: they show as a lower share.

  HBM bytes      D * (1 + M/K)   the data read once, the parity written once
                 + 32 bytes of digest for every shard block written
  operations     the parity as a bit-matrix product over GF(2): every byte
                 position of a block multiplies an (8M x 8K) bit matrix by 8K
                 bits, 2 * 8M * 8K operations for K data bytes = 128 * M per
                 data byte;
                 + mxh256 over all K+M shards: a (256 x 8) product per
                 256-byte chunk, 16 operations per hashed byte, and the tree
                 above it an eighth of that per level: 16 * 8/7.

A degraded GET's work (`decode_work`) is the same arithmetic turned round:
the K rows read are D bytes in, the T rebuilt rows D * T/K out, 32 bytes of
digest for each of the K shard blocks verified, 128 * T operations a data
byte and mxh256 over the D bytes read.  With T = M it is an encode's parity.

The digest term is the chip's only under a digest the chip computes.  Under
`highwayhash256S` the program hashes on the host (its native kernel beats
the device form: `storage/bitrot_io.device_preferred`), so the chip is asked
for the parity or the rebuilt rows alone and both functions leave the digest's
operations and bytes out (`HOST_HASHED`).

Both products are exact in int8 with int32 sums, so the least time counts
them at the chip's int8 peak, the faster of its two: a share of this bound
cannot pass 100 % by a change of number format.  (Held against the bf16 peak
the parity product alone gives ROADMAP Speed 7's 385 GB/s for EC:8+4;
`bf16_parity_gbps` returns that figure for the self-check.)
"""

from __future__ import annotations

import json
import os

BLOCK = 1 << 20
DIGEST = 32
MXH_OPS_PER_BYTE = 16.0 * 8.0 / 7.0
# Configurations' `bitrot_algo`s whose digests are the host's work.
HOST_HASHED = ("highwayhash256S",)


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise KeyError(f"peaks.json has no device kind {device_kind!r}: "
                       f"add it with its source, there is no default")
    return table[device_kind]


def encode_work(data_bytes: float, k: int, m: int,
                algo: str = "mxh256") -> dict:
    """{"ops", "hbm_bytes"} for `data_bytes` of PUT data at k+m, framed
    with the bitrot digest `algo`."""
    shard_bytes = data_bytes * (1.0 + m / k)
    blocks = data_bytes / BLOCK
    hashed = algo not in HOST_HASHED
    return {"ops": 128.0 * m * data_bytes
            + hashed * MXH_OPS_PER_BYTE * shard_bytes,
            "hbm_bytes": shard_bytes + hashed * DIGEST * (k + m) * blocks}


def decode_work(data_bytes: float, k: int, t: int,
                algo: str = "mxh256") -> dict:
    """{"ops", "hbm_bytes"} for `data_bytes` of GET data read from k
    shards, of which t data shards are rebuilt from the k rows read and
    verified against `algo`'s digests."""
    blocks = data_bytes / BLOCK
    hashed = algo not in HOST_HASHED
    return {"ops": (128.0 * t + hashed * MXH_OPS_PER_BYTE) * data_bytes,
            "hbm_bytes": data_bytes * (1.0 + t / k)
            + hashed * DIGEST * k * blocks}


def least_seconds(w: dict, device_kind: str) -> dict:
    """The larger of operations / peak and bytes / peak, and which, for
    the work `w` (`encode_work`, `decode_work`)."""
    p = peaks(device_kind)
    t_ops = w["ops"] / p["int8_ops_per_s"]
    t_hbm = w["hbm_bytes"] / p["hbm_bytes_per_s"]
    return {"seconds": max(t_ops, t_hbm), "ops_s": t_ops, "hbm_s": t_hbm,
            "bound": "hbm" if t_hbm >= t_ops else "int8 mxu"}


def hbm_gbps(k: int, m: int, device_kind: str) -> float:
    """Data GB/s at which parity alone saturates HBM (digests left out);
    with m = the rows rebuilt, a decode's."""
    return peaks(device_kind)["hbm_bytes_per_s"] / (1.0 + m / k) / 1e9


def bf16_parity_gbps(m: int, device_kind: str) -> float:
    """Data GB/s at which the parity product alone saturates the bf16 MXU."""
    return peaks(device_kind)["bf16_flops_per_s"] / (128.0 * m) / 1e9
