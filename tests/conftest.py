"""Test configuration: force an 8-device virtual CPU mesh before jax use.

Multi-chip sharding logic is tested on virtual CPU devices (no multi-chip TPU
hardware in CI); chip_smoke.py runs on the real chip outside pytest.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402  (after the jax platform pinning above)


def pytest_configure(config):
    # No pytest.ini in this repo: register markers here so tier-1's
    # `-m "not slow"` deselects the stress/load tests without warnings.
    config.addinivalue_line(
        "markers",
        "slow: stress/load tests excluded from the tier-1 run")
    config.addinivalue_line(
        "markers",
        "chaos: seeded fault-injection tests (smoke subset runs in "
        "tier-1; the full soak matrix is also marked slow)")
    config.addinivalue_line(
        "markers",
        "crash: kill-9 durability tests driving real server "
        "subprocesses through MTPU_CRASH points (a one-point smoke "
        "runs in tier-1; the full matrix is also marked slow — "
        "select with -m 'crash and slow')")
    config.addinivalue_line(
        "markers",
        "netchaos: partition-tolerance tests driving a multi-node "
        "cluster under the seeded network-chaos proxy (a one-scenario "
        "smoke runs in tier-1; the full partition/node-kill matrix is "
        "also marked slow — select with -m 'netchaos and slow')")
    config.addinivalue_line(
        "markers",
        "decom: pool decommission tests (in-process drain smoke runs "
        "in tier-1; the kill-9 mid-drain resume sweep over real "
        "server subprocesses is also marked slow — select with "
        "-m 'decom and slow')")
    config.addinivalue_line(
        "markers",
        "repl: replication-under-fire tests (journal replay, "
        "versioned fidelity and proxy-read smoke run in tier-1; the "
        "kill-9 repl.* matrix, the 2000-object resync kill and the "
        "two-cluster partition scenarios are also marked slow — "
        "select with -m 'repl and slow')")


@pytest.fixture(params=["1", "0"], ids=["fastpath", "oracle"])
def fastpath_mode(request, monkeypatch):
    """Tier-1 guard for the healthy-read fast path: every test that uses
    this fixture runs twice — once on the verify-only fast path
    (MTPU_GET_FASTPATH=1, the default) and once on the fused
    verify+decode oracle path (=0) — so the two implementations stay
    byte-exact under the same assertions."""
    monkeypatch.setenv("MTPU_GET_FASTPATH", request.param)
    return request.param


@pytest.fixture(params=["1", "0"], ids=["coalesce", "direct"])
def coalesce_mode(request, monkeypatch):
    """Oracle guard for cross-request dispatch coalescing: tests using
    this fixture run once through the DispatchCoalescer
    (MTPU_COALESCE=1, the default) and once on the direct-dispatch
    oracle (=0).  The singleton is retired on both edges so each run
    starts from a cold scheduler (no occupancy EMA or queued work
    bleeding between parametrizations)."""
    from minio_tpu.ops import coalesce

    coalesce.reset()
    monkeypatch.setenv("MTPU_COALESCE", request.param)
    yield request.param
    coalesce.reset()


@pytest.fixture(params=["1", "0"], ids=["metabatch", "metasolo"])
def metabatch_mode(request, monkeypatch):
    """Oracle guard for the batched metadata plane: tests using this
    fixture run once through the per-drive write MetaLanes
    (MTPU_METABATCH=1, the default — group-commit publishes) and once
    on the single-op oracle (=0).
    The singleton is retired on both edges so each run starts from
    cold lanes."""
    from minio_tpu.ops import metalanes

    metalanes.reset()
    monkeypatch.setenv("MTPU_METABATCH", request.param)
    yield request.param
    metalanes.reset()


@pytest.fixture(params=["1", "0"], ids=["hedge", "nohedge"])
def hedge_mode(request, monkeypatch):
    """Oracle guard for hedged shard reads: tests using this fixture
    run once with speculative parity reads armed (MTPU_HEDGE=1, the
    default) and once on the sequential oracle (=0) — results must be
    byte-identical; hedging may only change latency."""
    monkeypatch.setenv("MTPU_HEDGE", request.param)
    return request.param


@pytest.fixture(params=["1", "0"], ids=["lanes", "hashlib"])
def digest_mode(request, monkeypatch):
    """Oracle guard for the native multi-buffer digest plane: tests
    using this fixture run once on the shared SIMD MD5 lanes + batched
    sha256 (MTPU_NATIVE_DIGEST=1, the default) and once on the hashlib
    oracle (=0) — ETags, Content-MD5 verdicts, and streaming-SigV4
    decisions must be byte-identical."""
    monkeypatch.setenv("MTPU_NATIVE_DIGEST", request.param)
    return request.param


@pytest.fixture(params=["1", "0"], ids=["hotcache", "nocache"])
def hotcache_mode(request, monkeypatch):
    """Oracle guard for the RAM hot-object tier: tests using this
    fixture run once with the verified shared-memory cache armed
    (MTPU_HOTCACHE=1, the default) and once on the direct-read oracle
    (=0) — GET/ranged-GET/HEAD results must be byte-identical; the
    cache may only change latency."""
    monkeypatch.setenv("MTPU_HOTCACHE", request.param)
    return request.param


@pytest.fixture(params=["1", "0"], ids=["ilm", "noilm"])
def ilm_mode(request, monkeypatch):
    """Oracle guard for the data-temperature plane: tests using this
    fixture run once with scanner-driven transitions armed (MTPU_ILM=1,
    the default) and once with the plane disabled (=0) — objects the
    oracle run keeps hot and the ILM run serves through stubs must stay
    byte-identical on GET/ranged-GET/HEAD."""
    monkeypatch.setenv("MTPU_ILM", request.param)
    return request.param


@pytest.fixture(params=["1", "0"], ids=["zerocopy", "oracle"])
def zerocopy_mode(request, monkeypatch):
    """Oracle guard for the zero-copy data path: tests using this
    fixture run once with gather-write/sendfile responses, arena-view
    hot hits, and vectored shard IO armed (MTPU_ZEROCOPY=1, the
    default) and once on the buffered/copying oracle (=0) — every
    byte on the wire (plain, ranged, suffix, conditional, aws-chunked)
    must be identical between the two runs."""
    monkeypatch.setenv("MTPU_ZEROCOPY", request.param)
    return request.param


@pytest.fixture(params=["1", "0"], ids=["devcache", "upload"])
def devcache_mode(request, monkeypatch):
    """Oracle guard for the device-resident shard cache: tests using
    this fixture run once with verified shard batches cached on device
    (MTPU_DEVCACHE=1, the default) and once on the always-upload
    oracle (=0) — GET/ranged-GET/HEAD bodies and heal end-state must be
    byte-identical; the cache may only change how many bytes cross the
    host->device boundary.  The singleton is retired on both edges so
    resident entries and generation counters never bleed between
    parametrizations."""
    from minio_tpu.ops import devcache

    devcache.reset()
    monkeypatch.setenv("MTPU_DEVCACHE", request.param)
    yield request.param
    devcache.reset()


@pytest.fixture(params=["1", "0"], ids=["pipelined", "serial"])
def h2d_mode(request, monkeypatch):
    """Oracle guard for the double-buffered H2D staging pipeline: tests
    using this fixture run once with lanes shipping batch N+1 while
    batch N executes (MTPU_H2D_PIPELINE=1, the default) and once on the
    serial per-dispatch upload oracle (=0) — digests, parity, and
    rebuilt shards must be byte-identical.  The coalescer is retired on
    both edges so staged leases and pending launches never straddle the
    flag flip."""
    from minio_tpu.ops import coalesce, devcache

    coalesce.reset()
    devcache.reset_h2d()
    monkeypatch.setenv("MTPU_H2D_PIPELINE", request.param)
    yield request.param
    coalesce.reset()
    devcache.reset_h2d()


@pytest.fixture(params=["1", "0"], ids=["breaker", "nobreaker"])
def breaker_mode(request, monkeypatch):
    """Oracle guard for the drive circuit breaker: MTPU_BREAKER=0 pins
    every HealthWrappedDrive to passive stats-only behavior (always
    "ok", no fast-fail, no exclusion)."""
    monkeypatch.setenv("MTPU_BREAKER", request.param)
    return request.param
