"""Device discovery + deterministic erasure-set → device affinity.

The reference spreads objects across erasure sets with sipHashMod
(cmd/erasure-server-pool.go, mirrored by `engine/sets.py:set_for`).
This module pushes the SAME deterministic index one layer down, to the
accelerator plane:

    device = set_index % n_devices()

so kernel-lane placement needs no coordination protocol: it is stable
across boots, identical in every process of the pre-fork pool (all of
them derive it from the deployment-id-keyed sipHashMod), and trivially
rebalances when the device count changes — exactly the properties the
set placement already has.

Env:

- MTPU_DEVICES=N — lane count override, clamped to the visible device
  topology.  `=1` is the byte-identical single-lane oracle the
  differential tests diff against.  Unset, the count defaults to every
  visible device on a real TPU mesh and 1 on host backends, so CPU CI
  opts into multi-lane explicitly (simulated mesh via
  XLA_FLAGS=--xla_force_host_platform_device_count=8 + MTPU_DEVICES=8).

The env var is read per call so tests can flip lane counts without
re-importing; only the (static per-process) jax device topology is
cached.
"""

from __future__ import annotations

import os
import sys
import threading

#: (devices, platform, device_kind, device_count) — the ONE answer to
#: "what does this deployment compute on".  `devices` holds the jax
#: Device objects in the process that owns them and is empty in a
#: pre-fork pool worker, which adopts the owner's answer instead of
#: asking JAX (a chip belongs to one process).
_VISIBLE: tuple[list, str, str, int] | None = None


def _visible() -> tuple[list, str, str, int]:
    """Cached; device topology is fixed per process.  Import of jax is
    deferred to first use so import-light processes (the pre-fork
    supervisor) never pay for it.  A jax that cannot start raises: it
    is an error, not a host lane."""
    global _VISIBLE
    if _VISIBLE is None:
        import jax

        _count_compiles(jax)
        devs = list(jax.devices())
        _VISIBLE = (devs, jax.default_backend(), devs[0].device_kind,
                    len(devs))
    return _VISIBLE


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_compile_tl = threading.local()
_compile_listener = False


def _count_compiles(jax) -> None:
    """Count what XLA compiles in this process (once, where the device
    owner initialises JAX): `mtpu_jit_compiles_total` and
    `mtpu_jit_compile_seconds_total`.  JAX reports the duration of
    "compile or load from the persistent cache" on the compiling
    thread, and just before it, on a cache hit, the retrieval time: a
    hit is no compile and is not counted.  The listener runs on the
    thread that stalled, so the compile also lands as `device.compile`
    on whatever span is current there: the request that met a
    first-sight shape, or the lane's dispatch."""
    global _compile_listener
    if _compile_listener:
        return
    _compile_listener = True
    from ..observe import span as ospan
    from ..observe.metrics import DATA_PATH

    def on_duration(event: str, secs: float, **_kw) -> None:
        if event == _CACHE_HIT_EVENT:
            _compile_tl.hit = True
        elif event == _COMPILE_EVENT:
            if getattr(_compile_tl, "hit", False):
                _compile_tl.hit = False
                return
            DATA_PATH.record_jit_compile(secs)
            ospan.record("device.compile", secs)

    from jax import monitoring

    monitoring.register_event_duration_secs_listener(on_duration)


def adopt(platform: str, kind: str, count: int) -> None:
    """Pool worker: take the device owner's answer.  The worker holds
    no device itself (`jax_device` is None), so anything it decides
    from the platform routes to the owner."""
    global _VISIBLE
    _VISIBLE = ([], platform, kind, int(count))


def on_tpu() -> bool:
    """Whether the deployment's shard math runs on a TPU."""
    return _visible()[1] == "tpu"


def local_tpu() -> bool:
    """on_tpu() AND this process is the one holding the chip."""
    devs, plat, _, _ = _visible()
    return plat == "tpu" and bool(devs)


def describe() -> dict:
    """The four values the boot line and healthinfo's `device` block
    carry, plus whether THIS process has initialised a jax backend (a
    pool worker must answer false: the devices are its owner's)."""
    _, plat, kind, count = _visible()
    xb = sys.modules.get("jax._src.xla_bridge")
    return {"platform": plat, "kind": kind, "count": count,
            "lanes": n_devices(),
            "in_process": bool(xb and xb.backends_are_initialized())}


def boot_line(info: dict | None = None) -> str:
    """The one line a serving process prints about its devices."""
    d = info or describe()
    return (f"minio_tpu: device platform={d['platform']} "
            f"kind={d['kind']!r} count={d['count']} lanes={d['lanes']}")


def visible_count() -> int:
    return _visible()[3]


def n_devices() -> int:
    """Number of kernel lanes (= devices) the coalescer shards over."""
    v = os.environ.get("MTPU_DEVICES", "").strip()
    if v:
        try:
            n = int(v)
        except ValueError:
            n = 1
        return max(1, min(n, visible_count()))
    _, plat, _, count = _visible()
    return count if plat == "tpu" else 1


def device_for_set(set_index: int) -> int:
    """Lane affinity of an erasure set: same modulo-of-deterministic-
    index scheme as its sipHashMod placement, one layer down."""
    return int(set_index) % n_devices()


def jax_device(idx: int):
    """The jax Device lane `idx` dispatches on (None in a pool worker,
    which holds none).  Indices wrap over the visible topology so a
    lane index is always placeable."""
    devs = _visible()[0]
    if not devs:
        return None
    return devs[int(idx) % len(devs)]


def put(x, device_idx: int | None):
    """Commit `x` onto lane `device_idx`'s device via jax.device_put;
    identity when placement is unavailable or unrequested.  A committed
    input makes every downstream jit execution follow it to that
    device — the whole of 'explicit device placement' for the fused
    kernels."""
    if device_idx is None:
        return x
    dev = jax_device(device_idx)
    if dev is None:
        return x
    import jax

    if isinstance(x, jax.Array):
        # Already device-resident (lane staging upload / devcache):
        # the crossing was counted where it happened.
        return x
    from . import devcache

    devcache.note_h2d(int(getattr(x, "nbytes", 0) or 0), device_idx)
    return jax.device_put(x, dev)


def _reset_after_fork() -> None:
    # The supervisor forks before anything asks; a child asks (owner)
    # or adopts (worker) for itself.
    global _VISIBLE
    _VISIBLE = None


os.register_at_fork(after_in_child=_reset_after_fork)
