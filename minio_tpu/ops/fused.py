"""Fused bitrot-verify + erasure-transform: one dispatch, one HBM pass.

North-star config #5 (BASELINE.json): the reference verifies each shard
block's bitrot hash at read time (cmd/bitrot-streaming.go:142) and then
reconstructs missing shards with a separate SIMD pass
(cmd/erasure-decode.go:206). Here both run as ONE jitted device program
over the same (B, K, S) shard batch:

  - digests: the per-shard-block bitrot digest of every input row —
    mxh256 (MXU int8 matmuls, ops/mxhash_jax.py) or HighwayHash256
    (VPU scan, ops/highwayhash_jax.py) depending on the object's
    recorded algorithm,
  - targets: the GF(2^8) bit-plane matmul on the MXU reconstructing the
    requested rows.

XLA schedules the hash and the erasure matmul from the same HBM-resident
input, so the shard bytes cross HBM once instead of twice. The host
compares the 32-byte digests against the frame hashes (tiny) and decides
quorum / spare-read policy exactly like the unfused path.

Also provides the PUT-side fusion: encode parity AND hash all k+m shard
rows in one dispatch (the streaming-bitrot writer analogue,
cmd/bitrot-streaming.go:35).
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from . import devcache
from . import devices as devices_mod
from . import erasure_jax, erasure_pallas
from .highwayhash import MAGIC_KEY
from .highwayhash_jax import _hh256_impl
from .mxhash_jax import mxh256_rows

# Algorithms with a device digest kernel (usable in the fused paths).
DEVICE_ALGOS = ("mxh256", "highwayhash256S", "highwayhash256")


class Program:
    """`fn` jitted under a name that says what the program is (the
    profiler's `XLA Modules` line and the compile log then tell an
    encode from a GET-verify from a hash-only program: `jit_<name>`),
    plus the executables built ahead of time for it, one per (input
    shape, lane device).  Calling a shape that is built compiles
    nothing, whatever thread calls; any other shape goes through the
    jit and compiles on first sight.  The dispatch lanes size a batch
    by what is built here (ops/coalesce.py: the shape ladder).

    `operand` is the (shape, dtype) of a second input whose shape does
    not follow the batch's (the decode program's matrix): what varies
    between calls of one executable without compiling another."""

    def __init__(self, name: str, fn, operand: tuple | None = None):
        fn.__name__ = fn.__qualname__ = name
        self.name = name
        self.jit = jax.jit(fn)
        self._operand = operand
        self._built: dict[tuple, object] = {}
        self._build_mu = threading.Lock()

    def __call__(self, x, device: int | None = None, *operand):
        exe = self._built.get((x.shape, device))
        return (self.jit if exe is None else exe)(x, *operand)

    def built(self, shape: tuple, device: int | None) -> bool:
        return (tuple(shape), device) in self._built

    def build(self, shape: tuple, device: int | None) -> None:
        """Compile (or load from the persistent cache) the executable
        for a uint8 input of `shape` committed to lane `device`, as the
        lanes' staged uploads are.  Nothing to do in a process that
        holds no device."""
        dev = None if device is None else devices_mod.jax_device(device)
        if dev is None:
            return
        with self._build_mu:        # two who ask at once: one compiles
            if self.built(shape, device):
                return
            on_dev = jax.sharding.SingleDeviceSharding(dev)
            specs = [jax.ShapeDtypeStruct(tuple(shape), jnp.uint8,
                                          sharding=on_dev)]
            if self._operand is not None:
                specs.append(jax.ShapeDtypeStruct(*self._operand,
                                                  sharding=on_dev))
            self._built[tuple(shape), device] = \
                self.jit.lower(*specs).compile()


def _placed(x, device: int | None):
    """Commit the input batch to lane `device`'s jax device (PR 10
    erasure-set affinity): jit executions follow a committed input, so
    this one device_put is the whole placement story for every fused
    kernel. `device=None` keeps the historical default-device path.

    Inputs that are ALREADY jax arrays (a coalescer lane's pipelined
    staging upload, a devcache-resident batch) pass straight through —
    they crossed the boundary once when they were placed, and the h2d
    ledger counted them there; re-placing would both double the
    crossing and double the count."""
    if isinstance(x, jax.Array):
        return x
    nbytes = int(getattr(x, "nbytes", 0) or 0)
    if device is None:
        devcache.note_h2d(nbytes)
        return jnp.asarray(x, dtype=jnp.uint8)
    dev = devices_mod.jax_device(device)
    if dev is None:
        devcache.note_h2d(nbytes)
        return jnp.asarray(x, dtype=jnp.uint8)
    devcache.note_h2d(nbytes, device)
    return jax.device_put(jnp.asarray(x, dtype=jnp.uint8), dev)


def _digest_rows(x2d: jax.Array, algo: str, key: bytes) -> jax.Array:
    """(n, S) uint8 -> (n, 32) digests with the algo's device kernel."""
    if algo == "mxh256":
        return mxh256_rows(x2d)
    if algo in ("highwayhash256S", "highwayhash256"):
        return _hh256_impl(x2d, key)
    raise ValueError(f"no device kernel for bitrot algo {algo!r}")


@functools.lru_cache(maxsize=16)
def _hash_rows2d_jit(algo: str, key: bytes):
    def fn(x):  # (N, S) uint8
        return _digest_rows(x, algo, key)
    return Program(f"hash_rows_{algo}", fn)


def hash_rows_program(algo: str, key: bytes = MAGIC_KEY) -> Program:
    return _hash_rows2d_jit(algo, key)


def hash_rows_async(x, algo: str, key: bytes = MAGIC_KEY,
                    device: int | None = None):
    """(N, S) rows -> (N, 32) digests as an UNSYNCED jax array — the
    coalescer lanes' pipelined digest form (the caller resolves via
    np.asarray one dispatch later).  `x` may already be device-resident
    (counted at its placement site), on lane `device`."""
    if not isinstance(x, jax.Array):
        devcache.note_h2d(int(getattr(x, "nbytes", 0) or 0))
        x = jnp.asarray(x, dtype=jnp.uint8)
    return _hash_rows2d_jit(algo, key)(x, device)


@functools.lru_cache(maxsize=16)
def _hash_rows_jit(algo: str, key: bytes):
    def fn(x):  # (B, K, S) uint8
        b, kk, s = x.shape
        return _digest_rows(x.reshape(b * kk, s), algo, key).reshape(
            b, kk, 32)
    return Program(f"verify_{algo}", fn)


def verify_transform_name(k: int, m: int, algo: str | None) -> str:
    """The decode program of a geometry, as the profiler, the compile
    log and the lanes' `lane.dispatch` span name it; `algo` None names
    the digest-free one (the rebuild of a host-hashed algorithm)."""
    if algo is None:
        return f"transform_k{k}m{m}"
    return f"verify_transform_k{k}m{m}_{algo}"


@functools.lru_cache(maxsize=64)
def _verify_transform_jit(k: int, m: int, algo: str | None, key: bytes):
    def fn(x, mat):  # x: (B, K, S) uint8 rows; mat: their `decode_matrix`
        b, kk, s = x.shape
        digests = None if algo is None else _digest_rows(
            x.reshape(b * kk, s), algo, key).reshape(b, kk, 32)
        out = erasure_pallas.gf_matmul_blocks(mat, x, m)
        # A target row an output of its own: the caller fetches the T
        # it asked for, and the pad rows never leave the device.
        return digests, tuple(out[:, j] for j in range(m))

    return Program(verify_transform_name(k, m, algo), fn,
                   operand=((8 * m, 8 * k), jnp.bfloat16))


def decode_matrix(k: int, m: int, sources: tuple[int, ...],
                  targets: tuple[int, ...]) -> np.ndarray:
    """The decode program's operand: the plane-major bit matrix that
    maps rows `sources` to rows `targets`
    (`erasure_jax._transform_matrix_bits`, cached on the host),
    zero-padded from T to M target rows, so that its shape is the
    geometry's and not the loss pattern's."""
    t = len(targets)
    if not 0 < t <= m:
        raise ValueError(f"{t} rows to rebuild at EC:{k}+{m}")
    bits = erasure_jax._transform_matrix_bits(
        k, m, tuple(sources), tuple(targets))        # row j*T + target
    mat = np.zeros((8, m, 8 * k), dtype=jnp.bfloat16)
    mat[:, :t] = bits.reshape(8, t, 8 * k)
    return mat.reshape(8 * m, 8 * k)


def verify_and_transform(x, k: int, m: int, sources: tuple[int, ...],
                         targets: tuple[int, ...],
                         algo: str | None = "highwayhash256S",
                         key: bytes = MAGIC_KEY,
                         device: int | None = None):
    """((B, K, S) shard rows) -> ((B, K, 32) digests, T rebuilt rows of
    (B, S) each, in `targets` order; None where there are none).

    Digests are of the INPUT rows (callers compare them against the bitrot
    frame hashes); rebuilt rows are the GF transform sources->targets
    (`rows_on_host` brings them back as one (B, T, S) array).  With no
    targets (nothing missing) only the hash runs; with `algo` None (an
    algorithm the host hashes) only the rebuild, and the digests are
    None.  `device` is the coalescer-lane index the dispatch is placed
    on (None = default device, the pre-sharding behavior).
    """
    prog = verify_transform_program(k, m, sources, targets, algo, key)
    x = _placed(x, device)
    if not targets:
        return prog(x, device), None
    mat = decode_matrix(k, m, sources, targets)
    devcache.note_h2d(mat.nbytes, device)
    digests, rows = prog(x, device, mat)
    return digests, rows[:len(targets)]


def rows_on_host(rows, n: int | None = None) -> np.ndarray:
    """`verify_and_transform`'s rebuilt rows as one (n, T, S) array on
    the host, the crossing counted (the first `n` blocks: a lane's
    batch carries pad blocks behind them)."""
    return np.stack([devcache.fetch(r)[:n] for r in rows], axis=1)


def verify_transform_program(k: int, m: int, sources: tuple[int, ...],
                             targets: tuple[int, ...], algo: str | None,
                             key: bytes = MAGIC_KEY) -> Program:
    """The program `verify_and_transform` runs: the hash alone where
    nothing is to be rebuilt, else the geometry's one decode program,
    whatever (sources, targets): they reach it as its matrix operand.
    `algo` None: the geometry's one digest-free decode program, shared
    by every algorithm the host hashes."""
    if not targets:
        return _hash_rows_jit(algo, key)
    return _verify_transform_jit(k, m, algo, key)


@functools.lru_cache(maxsize=64)
def _encode_hash_jit(k: int, m: int, algo: str | None, key: bytes):
    mat = jnp.asarray(erasure_jax._encode_matrix_bits(k, m),
                      dtype=jnp.bfloat16)

    def fn(x):  # x: (B, K, S) uint8 data shards
        b, kk, s = x.shape
        parity = erasure_pallas.gf_matmul_blocks(mat, x, m)
        if algo is None:
            return parity, None
        with jax.named_scope("stack_for_hash"):
            full = jnp.concatenate([x, parity], axis=1)   # (B, K+M, S)
            rows = full.transpose(1, 0, 2).reshape((kk + m) * b, s)
        digests = _digest_rows(rows, algo, key).reshape(kk + m, b, 32)
        return parity, digests

    name = f"encode_k{k}m{m}" if algo is None \
        else f"encode_hash_k{k}m{m}_{algo}"
    return Program(name, fn)


def encode_hash_program(k: int, m: int, algo: str | None,
                        key: bytes = MAGIC_KEY) -> Program:
    return _encode_hash_jit(k, m, algo, key)


def encode_and_hash(x, k: int, m: int,
                    algo: str | None = "highwayhash256S",
                    key: bytes = MAGIC_KEY,
                    device: int | None = None):
    """((B, K, S) data) -> ((B, M, S) parity, (K+M, B, 32) digests).

    The PUT hot path: parity AND per-shard-block bitrot digests in one
    device dispatch; framing on the host is then pure byte interleaving.
    Digest layout is shard-major to match frame_shards_batch's
    (n_shards, n_blocks) order.  `algo` None (an algorithm the host
    hashes): the parity alone, digests None.  `device` places the
    dispatch on that coalescer lane's device (None = default device)."""
    return _encode_hash_jit(k, m, algo, key)(_placed(x, device), device)
