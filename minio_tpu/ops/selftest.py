"""Startup self-test guards — production sanity checks, not just pytest.

The reference hard-fails server boot if the erasure codec or bitrot hash
produce unexpected bytes (erasureSelfTest golden-xxhash table,
/root/reference/cmd/erasure-coding.go:158; bitrotSelfTest golden chain,
/root/reference/cmd/bitrot.go:214). Same contract here: a corrupted
build/toolchain must refuse to serve rather than write bad shards.

Kept fast (~ms): a handful of geometry configs through the CPU codec +
one encode/reconstruct round trip + the HighwayHash golden chain.
"""

from __future__ import annotations

import hashlib


class SelfTestError(RuntimeError):
    pass


def erasure_self_test() -> None:
    import numpy as np

    from .erasure_cpu import ReedSolomonCPU

    rng = np.random.default_rng(0xEC)
    for (k, m) in ((2, 2), (4, 2), (8, 4), (12, 4)):
        data = rng.integers(0, 256, size=k * 64, dtype=np.uint8).tobytes()
        rs = ReedSolomonCPU(k, m)
        shards = rs.encode_data(data)
        # Knock out `m` shards, reconstruct, compare.
        gone = list(range(0, 2 * m, 2))[:m]
        partial = [None if i in gone else s for i, s in enumerate(shards)]
        rec = rs.reconstruct(partial)
        for i in gone:
            if not np.array_equal(rec[i], shards[i]):
                raise SelfTestError(f"erasure self-test EC:{k}+{m} "
                                    f"reconstruct mismatch row {i}")


# Golden chain from the published HighwayHash algorithm with the magic
# bitrot key: digest of b"" then iterated digest-of-digest, pinned at
# build time from the scalar implementation (itself validated against
# the reference's constants in tests/test_highwayhash.py).
_HH_CHAIN_SHA256 = \
    "48883e06e9e249f4681c369484fc12a4f5f6891fde90a1a7be5a33288d46f3f2"


def bitrot_self_test() -> None:
    from .highwayhash import HighwayHash256

    h = b""
    for _ in range(8):
        hh = HighwayHash256()
        hh.update(h)
        h = hh.digest()
    if hashlib.sha256(h).hexdigest() != _HH_CHAIN_SHA256:
        raise SelfTestError("bitrot (HighwayHash256) self-test mismatch")


# Golden chain for mxh256 (the default write algorithm, ops/mxhash.py):
# digest of b"" then iterated digest-of-digest, pinned at build time from
# the exact-integer numpy spec implementation.
_MXH_CHAIN_SHA256 = \
    "d6373d19d83d8c7d0a34aa26414e76ea7ba722c0b0895b23e971fa4912566bc7"


def mxhash_self_test() -> None:
    from .mxhash import mxh256

    h = b""
    for _ in range(8):
        h = mxh256(h)
    if hashlib.sha256(h).hexdigest() != _MXH_CHAIN_SHA256:
        raise SelfTestError("bitrot (mxh256) self-test mismatch")


def digest_self_test() -> None:
    """Validate EVERY compiled native digest path (not just the one
    runtime dispatch would pick) against hashlib before serving: a
    miscompiled SIMD body must refuse to boot, same contract as the
    erasure/bitrot golden tests.  Skips silently when the native lib is
    unavailable or disabled — the hashlib oracle needs no check."""
    from ..utils import digestlanes
    if not digestlanes.use_native():
        return
    from native import digest_native as dn

    # Sizes straddling every padding boundary (RFC 1321 / FIPS 180-4:
    # 55/56/57 one-vs-two pad blocks, 63/64/65 block edges).
    sizes = (0, 1, 55, 56, 57, 63, 64, 65, 1000)
    bufs = [bytes((i * 37 + j) % 256 for j in range(n))
            for i, n in enumerate(sizes)]
    for isa in dn.supported_md5_isas():
        got = dn.md5_batch(bufs, isa)
        want = [hashlib.md5(b).digest() for b in bufs]
        if got != want:
            raise SelfTestError(
                f"md5 self-test mismatch on {dn.MD5_ISA_NAMES[isa]}")
    for isa in dn.supported_sha_isas():
        got = dn.sha256_batch(bufs, isa)
        want = [hashlib.sha256(b).digest() for b in bufs]
        if got != want:
            raise SelfTestError(
                f"sha256 self-test mismatch on {dn.SHA_ISA_NAMES[isa]}")


def device_lane_self_test() -> None:
    """Encode+hash golden vectors on EVERY configured device lane before
    serving (PR 10 device sharding): a device whose compiled kernels or
    HBM produce wrong bytes must refuse to boot, named by index, rather
    than corrupt the slice of erasure sets affine to it.  Single-lane
    hosts run exactly one pass (the historical default-device check).
    Runs in the process that holds the devices — in a pre-fork pool
    that is the device owner, never a worker."""
    import numpy as np

    from . import devices as devices_mod
    from . import fused
    from .erasure_cpu import ReedSolomonCPU
    from .mxhash import mxh256

    k, m, s = 2, 2, 128
    rng = np.random.default_rng(0xD0D)
    x = rng.integers(0, 256, size=(1, k, s), dtype=np.uint8)
    rs = ReedSolomonCPU(k, m)
    want_parity = np.stack(
        rs.encode([x[0, i] for i in range(k)])[k:], axis=0)
    rows = np.concatenate([x[0], want_parity], axis=0)
    want_digests = [mxh256(rows[i].tobytes()) for i in range(k + m)]
    for dev in range(devices_mod.n_devices()):
        try:
            parity, digests = fused.encode_and_hash(
                x, k, m, algo="mxh256", device=dev)
            parity = np.asarray(parity)[0]
            digests = np.asarray(digests)[:, 0]
        except Exception as e:  # noqa: BLE001 — name the device
            raise SelfTestError(
                f"device lane self-test dispatch failed on device "
                f"{dev}: {e}") from e
        if not np.array_equal(parity, want_parity):
            raise SelfTestError(
                f"device lane self-test encode mismatch on device {dev}")
        if [d.tobytes() for d in digests] != want_digests:
            raise SelfTestError(
                f"device lane self-test digest mismatch on device {dev}")


def metrics_registry_self_test() -> None:
    """Every exported metric family must carry a help string, live in
    the mtpu_ namespace, and appear in the README's Observability
    section — boot-time drift guard: a family added without docs
    refuses to serve.  The README may name families via brace groups
    (mtpu_api_last_minute_{p50,p99}) or trailing-* wildcards
    (mtpu_worker_*); an absent README (stripped install) skips the doc
    check, never the help/namespace check."""
    import re
    from pathlib import Path

    from ..observe.metrics import MetricsRegistry

    fams = MetricsRegistry().families()
    if not fams:
        raise SelfTestError("metrics registry exports no families")
    names = []
    for m in fams:
        if not getattr(m, "help", ""):
            raise SelfTestError(
                f"metric family {m.name} has no help string")
        if not m.name.startswith("mtpu_"):
            raise SelfTestError(
                f"metric family {m.name} outside the mtpu_ namespace")
        names.append(m.name)
    readme = Path(__file__).resolve().parents[2] / "README.md"
    try:
        text = readme.read_text(encoding="utf-8")
    except OSError:
        return
    documented: set[str] = set()
    prefixes: list[str] = []
    for tok in re.findall(r"mtpu_[\w{},*]+", text):
        if "{" in tok and "}" in tok:
            base, rest = tok.split("{", 1)
            inner, tail = rest.split("}", 1)
            for alt in inner.split(","):
                documented.add(base + alt + tail)
        elif tok.endswith("*"):
            prefixes.append(tok[:-1])
        else:
            documented.add(tok)
    missing = [n for n in names
               if n not in documented
               and not any(n.startswith(p) for p in prefixes)]
    if missing:
        raise SelfTestError(
            "metric families missing from the README metrics table: "
            + ", ".join(sorted(missing)))


def run_startup_self_tests(device: bool = True) -> None:
    """`device=False` in a pool worker: the device owner runs the
    device-lane test, since a chip belongs to one process."""
    erasure_self_test()
    bitrot_self_test()
    mxhash_self_test()
    digest_self_test()
    if device:
        device_lane_self_test()
    metrics_registry_self_test()
    # Fail boot on a misconfigured bitrot write algorithm (clear config
    # error now, not a confusing per-request failure later).
    from ..storage.bitrot_io import write_algo
    write_algo()
