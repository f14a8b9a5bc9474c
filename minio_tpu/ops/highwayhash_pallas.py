"""Pallas TPU kernel for multi-stream HighwayHash-256 bulk packets.

The XLA scan formulation (highwayhash_jax) pays per-op dispatch overhead
on every one of thousands of sequential packets. This kernel moves the
WHOLE packet chain inside one Mosaic program: state lives in VMEM
scratch, packets stream through in (PB, 4, S) chunks via the pipeline,
and the packet-chunk grid dimension is sequential so scratch carries the
chain across chunks.

Layout notes (what made it fast): every 64-bit lane is TWO SEPARATE 1-D
(S,) uint32 arrays — 32 state arrays total. The (4, S) formulation with
`.at[lane].set` updates (fine under XLA) materializes whole-array copies
per zipper step inside Mosaic; unrolled per-lane scalars keep each op a
plain elementwise vreg instruction.

Only the bulk multiple-of-32 prefix runs here; remainder packets and
finalization reuse the (bit-identical) XLA path, which also serves as
the correctness oracle in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import highwayhash_jax as hj

PB = 64           # packets per pipelined chunk
SBLK = 1024       # streams per program: wide 1-D ops keep the VPU busy
#                   despite the serial packet chain.


def _update_lanes(st: tuple, lanes: tuple) -> tuple:
    """One packet, fully unrolled per lane.

    st: 32-tuple of (S,) uint32 — [group v0,v1,mul0,mul1] x [lane 0..3]
    x [hi,lo]; lanes: 8-tuple (lane0_hi, lane0_lo, ... lane3_lo).
    """
    add64, xor64 = hj._add64, hj._xor64
    mul = hj._mul32x32

    def g(group, lane):                       # -> (hi, lo)
        base = group * 8 + lane * 2
        return (st[base], st[base + 1])

    v0 = [g(0, i) for i in range(4)]
    v1 = [g(1, i) for i in range(4)]
    mul0 = [g(2, i) for i in range(4)]
    mul1 = [g(3, i) for i in range(4)]

    for i in range(4):
        lane = (lanes[2 * i], lanes[2 * i + 1])
        v1[i] = add64(add64(v1[i], mul0[i]), lane)
        mul0[i] = xor64(mul0[i], mul(v1[i][1], v0[i][0]))
        v0[i] = add64(v0[i], mul1[i])
        mul1[i] = xor64(mul1[i], mul(v0[i][1], v1[i][0]))
    for (i0, i1) in ((0, 1), (2, 3)):
        a0, a1 = hj._zipper_addend(v1[i0], v1[i1])
        v0[i0] = add64(v0[i0], a0)
        v0[i1] = add64(v0[i1], a1)
    for (i0, i1) in ((0, 1), (2, 3)):
        a0, a1 = hj._zipper_addend(v0[i0], v0[i1])
        v1[i0] = add64(v1[i0], a0)
        v1[i1] = add64(v1[i1], a1)

    out = []
    for group in (v0, v1, mul0, mul1):
        for pair in group:
            out.extend(pair)
    return tuple(out)


def _kernel(hi_ref, lo_ref, out_ref, st_ref, *, init: np.ndarray):
    import jax.experimental.pallas as pl

    k = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(k == 0)
    def _():
        st_ref[...] = jnp.broadcast_to(
            jnp.asarray(init, dtype=jnp.uint32)[:, None], st_ref.shape)

    state = tuple(st_ref[w] for w in range(32))

    def body(i, st):
        lanes = []
        for lane in range(4):
            lanes.append(hi_ref[i, lane])
            lanes.append(lo_ref[i, lane])
        return _update_lanes(st, tuple(lanes))

    state = jax.lax.fori_loop(0, hi_ref.shape[0], body, state)
    st_ref[...] = jnp.stack(state)

    @pl.when(k == nk - 1)
    def _():
        out_ref[...] = st_ref[...]


@functools.lru_cache(maxsize=32)
def _bulk_fn(p: int, s: int, key: bytes):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # init words, flattened in kernel state order.
    k = np.frombuffer(key, dtype="<u8")
    i0 = np.array(hj.INIT0, dtype=np.uint64)
    i1 = np.array(hj.INIT1, dtype=np.uint64)
    krot = (k >> np.uint64(32)) | (k << np.uint64(32))
    init = np.empty(32, dtype=np.uint32)
    for gi, v in enumerate((i0 ^ k, i1 ^ krot, i0, i1)):
        for lane in range(4):
            init[gi * 8 + lane * 2] = np.uint32(v[lane] >> np.uint64(32))
            init[gi * 8 + lane * 2 + 1] = np.uint32(
                v[lane] & np.uint64(0xFFFFFFFF))

    grid = (s // SBLK, p // PB)
    return pl.pallas_call(
        functools.partial(_kernel, init=init),
        grid=grid,
        in_specs=[
            pl.BlockSpec((PB, 4, SBLK), lambda j, kk: (kk, 0, j)),
            pl.BlockSpec((PB, 4, SBLK), lambda j, kk: (kk, 0, j)),
        ],
        out_specs=pl.BlockSpec((32, SBLK), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((32, s), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((32, SBLK), jnp.uint32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )


def bulk_state(hi: jax.Array, lo: jax.Array, key: bytes):
    """Run the bulk packets through the kernel; returns the state dict in
    highwayhash_jax's (4, S)-pair format. hi/lo: (P, 4, S) uint32 with
    P % PB == 0 and S % SBLK == 0 (caller pads streams)."""
    p, _, s = hi.shape
    out = _bulk_fn(p, s, key)(hi, lo)          # (32, S)

    def group(gi):
        his = jnp.stack([out[gi * 8 + lane * 2] for lane in range(4)])
        los = jnp.stack([out[gi * 8 + lane * 2 + 1] for lane in range(4)])
        return (his, los)

    return {"v0": group(0), "v1": group(1),
            "mul0": group(2), "mul1": group(3)}


def supported(n_streams: int, n_packets: int) -> bool:
    """OFF by default (MTPU_HH_PALLAS=1 enables).

    Measured on v5e: this kernel reaches ~1 GB/s vs the XLA scan's
    ~2 GB/s at 1024 streams x 4096 packets — HighwayHash's dependent
    32x32->64 multiply chain has no fast VPU lowering (each mul is five
    16-bit partial products with carries), so in-kernel execution saves
    dispatch overhead but loses more to serialized emulated multiplies.
    Kept as the documented negative result for SURVEY §7 hard-part #3;
    the XLA scan remains the production device path. The env gate lives
    in hh256_batch_jax (part of the jit cache key); this checks only
    backend/shape feasibility.
    """
    from . import devices
    return (devices.local_tpu()
            and n_packets >= PB
            and n_streams >= SBLK // 4)
