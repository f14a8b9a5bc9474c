"""The served path's device programs compile for a v5e that is described,
not attached (guide on-chip-measurement §2): what the chip's compiler would
refuse — an unaligned slice, too much VMEM, a program that does not fit
16 GB of HBM, a kernel that cannot be partitioned — fails here, at no chip
time.  A compile that passes is not a chip run; chip_smoke.py is.

The programs are reached through the code the server runs: the test steers
the one device decision (ops/devices) by adopting a TPU answer, exactly as a
pool worker adopts its owner's, so `gf_matmul_blocks` and the fused programs
take their TPU branch unpatched.  All cases live in this one file and the
topology is described inside a module fixture: only one process may load
libtpu, and under pytest-xdist only the worker that runs this file does.
The HighwayHash device programs are left out (22 s each, off the served
path: they run only where no native HighwayHash builds).
"""

import os
import re

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

from minio_tpu.engine.erasure_set import BATCH_BLOCKS  # noqa: E402
from minio_tpu.ops import devices, erasure_pallas, fused  # noqa: E402
from minio_tpu.ops.highwayhash import MAGIC_KEY  # noqa: E402
from minio_tpu.parallel.sharded import ShardedCodec  # noqa: E402

HBM_BYTES = 16 * 10**9          # one v5e chip


def _forget_programs() -> None:
    for cached in (fused._encode_hash_jit, fused._verify_transform_jit,
                   fused._hash_rows_jit, fused._hash_rows2d_jit):
        cached.cache_clear()
    jax.clear_caches()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out.
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    saved = devices._VISIBLE
    # A program an earlier test of this process traced on the CPU
    # backend took its CPU branch: trace them anew.
    _forget_programs()
    devices.adopt("tpu", t.devices[0].device_kind, len(t.devices))
    yield t
    devices._VISIBLE = saved
    # The jitted programs traced their TPU branch: a later test in this
    # process must not be handed them.
    _forget_programs()
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.asarray(topo.devices).reshape(2, 2), ("blocks", "lanes"))


def _check(compiled, kernels: int) -> str:
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == kernels
    ma = compiled.memory_analysis()
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) < HBM_BYTES
    return text


def _kernel_outputs(text: str) -> list[str]:
    """The result shape of each tpu_custom_call in a compiled program."""
    return re.findall(r"= (u8\[[\d,]+\])\{[^}]*\} custom-call\(.*"
                      r'custom_call_target="tpu_custom_call"', text)


# (K, S, rows): 8+4 and 2+2 run whole 8 KiB lane tiles; K=6 (the server's
# default on 12 drives) and K=12 (16-drive EC:4) have S = ceil(1 MiB / K),
# which no such tile divides: the kernel takes S as it is (its last tile
# ragged) and writes (B, rows, S), with no pad before it or slice after.
@pytest.mark.parametrize("k,s,rows", [
    (8, 131072, 4), (2, 524288, 2), (6, 174763, 6), (12, 87382, 4)])
def test_gf_matmul_runs_the_kernel_for_every_geometry(one_chip, k, s, rows):
    mat = jax.ShapeDtypeStruct((8 * rows, 8 * k), jnp.bfloat16,
                               sharding=one_chip)
    x = jax.ShapeDtypeStruct((BATCH_BLOCKS, k, s), jnp.uint8,
                             sharding=one_chip)
    compiled = jax.jit(
        lambda mat, x: erasure_pallas.gf_matmul_blocks(mat, x, rows)
    ).lower(mat, x).compile()
    text = _check(compiled, kernels=1)
    assert _kernel_outputs(text) == [f"u8[{BATCH_BLOCKS},{rows},{s}]"]
    assert compiled.out_info.shape == (BATCH_BLOCKS, rows, s)


@pytest.mark.parametrize("program,kernels", [
    ("encode_and_hash", 1), ("verify_and_transform", 1), ("verify", 0)])
def test_fused_mxh256_programs(one_chip, program, kernels):
    k, m = 8, 4
    fn = {
        "encode_and_hash": lambda: fused._encode_hash_jit(
            k, m, "mxh256", MAGIC_KEY),
        # any rows lost: they reach the one program as its matrix
        "verify_and_transform": lambda: fused._verify_transform_jit(
            k, m, "mxh256", MAGIC_KEY),
        "verify": lambda: fused._hash_rows_jit("mxh256", MAGIC_KEY),
    }[program]()
    args = [jax.ShapeDtypeStruct((BATCH_BLOCKS, k, 131072), jnp.uint8,
                                 sharding=one_chip)]
    if program == "verify_and_transform":
        args.append(jax.ShapeDtypeStruct((8 * m, 8 * k), jnp.bfloat16,
                                         sharding=one_chip))
    _check(fn.jit.lower(*args).compile(), kernels)


# The shape ladder (ops/coalesce.py) runs the same programs at 1, 2, 4, 8
# and 16 blocks: the smallest and a middle step of both cells' geometry.
@pytest.mark.parametrize("k,m,s,blocks", [
    (2, 2, 524288, 1), (2, 2, 524288, 4), (8, 4, 131072, 1),
    (8, 4, 131072, 16)])
def test_ladder_steps_of_encode_and_get_digest(one_chip, k, m, s, blocks):
    enc = fused.encode_hash_program(k, m, "mxh256").jit.lower(
        jax.ShapeDtypeStruct((blocks, k, s), jnp.uint8,
                             sharding=one_chip)).compile()
    _check(enc, kernels=1)
    assert enc.out_info[0].shape == (blocks, m, s)
    assert enc.out_info[1].shape == (k + m, blocks, 32)
    dig = fused.hash_rows_program("mxh256").jit.lower(
        jax.ShapeDtypeStruct((blocks * k, s), jnp.uint8,
                             sharding=one_chip)).compile()
    _check(dig, kernels=0)
    assert dig.out_info.shape == (blocks * k, 32)


# The one decode program a geometry (PR 35): its matrix an operand, a
# rebuilt row an output of its own.  EC:6+6 reaches the kernel at
# S = 174,763 as it is, 64 blocks the batch the degraded GETs run.
@pytest.mark.parametrize("k,m,s,blocks", [
    (6, 6, 174763, 16), (6, 6, 174763, 1), (6, 6, 174763, 64),
    (8, 4, 131072, 16), (2, 2, 524288, 32)])
def test_ladder_steps_of_the_decode_program(one_chip, k, m, s, blocks):
    prog = fused.verify_transform_program(k, m, (), (0,), "mxh256")
    dec = prog.jit.lower(
        jax.ShapeDtypeStruct((blocks, k, s), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((8 * m, 8 * k), jnp.bfloat16,
                             sharding=one_chip)).compile()
    text = _check(dec, kernels=1)
    assert _kernel_outputs(text) == [f"u8[{blocks},{m},{s}]"]
    digests, rows = dec.out_info
    assert digests.shape == (blocks, k, 32)
    assert [r.shape for r in rows] == [(blocks, s)] * m


# A host-hashed algorithm (MinIO's default, highwayhash256S) rides the
# lanes on the digest-free programs: the parity, and the one decode
# program a geometry with no digest half, at the ladder's steps.
@pytest.mark.parametrize("k,m,s,blocks", [
    (8, 4, 131072, 32), (8, 4, 131072, 4), (6, 6, 174763, 16)])
def test_digest_free_programs_of_a_host_hashed_algorithm(one_chip, k, m, s,
                                                        blocks):
    x = jax.ShapeDtypeStruct((blocks, k, s), jnp.uint8, sharding=one_chip)
    enc = fused.encode_hash_program(k, m, None)
    assert enc.name == f"encode_k{k}m{m}"
    compiled = enc.jit.lower(x).compile()
    _check(compiled, kernels=1)
    assert compiled.out_info[0].shape == (blocks, m, s)
    assert compiled.out_info[1] is None
    dec = fused.verify_transform_program(k, m, (), (0,), None)
    assert dec.name == f"transform_k{k}m{m}"
    compiled = dec.jit.lower(x, jax.ShapeDtypeStruct(
        (8 * m, 8 * k), jnp.bfloat16, sharding=one_chip)).compile()
    _check(compiled, kernels=1)
    digests, rows = compiled.out_info
    assert digests is None
    assert [r.shape for r in rows] == [(blocks, s)] * m


@pytest.mark.parametrize("program", ["encode", "gather_reconstruct"])
def test_sharded_codec_on_the_2x2_mesh(mesh, program):
    sc = ShardedCodec(2, 2, mesh)
    if program == "encode":
        fn, spec = sc._encode_jit, P("blocks", None, "lanes")
    else:
        fn, spec = (sc.make_reconstruct_jit((1, 2), (0,)),
                    P("blocks", "lanes", None))
    x = jax.ShapeDtypeStruct((BATCH_BLOCKS, 2, 524288), jnp.uint8,
                             sharding=NamedSharding(mesh, spec))
    text = _check(fn.lower(x).compile(), kernels=1)
    assert ("all-gather" in text) == (program == "gather_reconstruct")
