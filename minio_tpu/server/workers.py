"""Pre-fork worker pool: N HTTP server processes + one device owner.

The GIL pins a single-process server at ~1 core no matter how many
handler threads run (ROADMAP Open item 1: 16-client aggregate BELOW
1-client).  The reference escapes this with goroutines over one shared
erasure backend (cmd/server-main.go:441); the Python-shaped equivalent
is the classic pre-fork design:

  supervisor (this module, light: no jax, no engine imports)
    |- device owner   the ONE process that initialises a JAX backend
    |                 (a chip belongs to one process): runs the
    |                 device-lane self-test, publishes platform /
    |                 device_kind / count in the control block,
    |                 runs the REAL DispatchCoalescer and serves the
    |                 shared-memory dispatch plane (ops/ipc_dispatch.py)
    |- worker 0       full S3 vertical; also the recovery owner:
    |                 host self-tests, boot recovery sweep, MRF
    |                 orphan-journal adoption, the data scanner
    |- worker 1..N-1  full S3 vertical

Workers adopt the owner's device answer (ops/devices.adopt) and never
ask JAX: device-bound kernels ship to the owner, and a worker's
JAX_PLATFORMS names no backend, so a stray JAX call in a worker raises
instead of racing the owner for the chip or computing on a quiet CPU
backend.

Every worker binds the SAME (host, port) with SO_REUSEPORT — the
kernel load-balances accepted connections across processes, so there
is no proxy hop and no fd passing.  Shard batches cross to the owner
through a preallocated ShmArena + ShmRing descriptor plane; nothing
bigger than 64 bytes is ever pickled.

Lifecycle (PR 7 contracts, one level up):
  * SIGTERM/SIGINT on the supervisor fans SIGTERM out to all workers;
    each drains (503 on new requests, inflight completes, digest lanes
    flush, MRF checkpoints) and exits 0; the owner is retired LAST so
    in-drain requests keep their dispatch plane; supervisor exits 0.
  * A second signal SIGKILLs everything (the escape hatch).
  * A worker that dies mid-serve is respawned after
    MTPU_RESPAWN_DELAY_S with its `mtpu_worker_respawns_total` slab
    counter bumped; the owner respawns under a NEW generation and
    workers re-attach automatically.
  * MTPU_CRASH crash points arm inside workers through the inherited
    environment.  When a crash harness is armed, a child exiting 137
    IS the experiment: the supervisor tears the pool down and exits
    137 itself, so kill-matrix drivers see the same contract as
    single-process mode.
  * Each child sets PR_SET_PDEATHSIG(SIGKILL): a kill -9 on the
    supervisor never leaves orphan workers squatting on the port.

`MTPU_WORKERS=0` (default) never enters this module — single-process
mode remains the tier-1 oracle.
"""

from __future__ import annotations

import errno
import mmap
import os
import signal
import socket
import sys
import threading
import time

import numpy as np

from ..ops.ipc_ring import ShmRing
from ..ops.shm_arena import ShmArena, default_arena_bytes

#: shared control block layout (all int64, single-writer per field)
_GHDR = 16                       # global slots
_WSLOTS = 10                     # per-worker slab stride
# global: 0 owner_gen, 1 owner_pid, 2 owner_beat_ns, 3 supervisor_pid,
#         4 nworkers, 5 owner_co_dispatches, 6 owner_co_items,
#         7 owner_co_pending, 8 owner_co_weight, 9 topology_gen,
#         10 device_count (0 = owner has not answered yet),
#         12 platform (8 ascii bytes), 13-15 device_kind (24 ascii bytes)
# worker: 0 pid, 1 beat_ns, 2 ready, 3 draining, 4 respawns,
#         5 requests_total, 6 inflight, 7 audit_dropped,
#         8 hotcache_hits, 9 hotcache_misses


def nworkers_env() -> int:
    try:
        return max(0, int(os.environ.get("MTPU_WORKERS", "0") or 0))
    except ValueError:
        return 0


def _respawn_delay_s() -> float:
    try:
        return max(0.0,
                   float(os.environ.get("MTPU_RESPAWN_DELAY_S", "0.5")))
    except ValueError:
        return 0.5


def _stale_s() -> float:
    from ..ops.ipc_dispatch import owner_stale_s
    return owner_stale_s()


def _now_ns() -> int:
    return time.monotonic_ns()


def _set_pdeathsig() -> None:
    """Die with the supervisor: PR_SET_PDEATHSIG(SIGKILL).  A kill -9
    on the parent must not leave this child holding the port."""
    try:
        import ctypes
        libc = ctypes.CDLL(None, use_errno=True)
        libc.prctl(1, signal.SIGKILL)       # PR_SET_PDEATHSIG == 1
    except Exception:  # noqa: BLE001 — non-Linux: supervised exit only
        pass


class SharedState:
    """The cross-process control block: owner generation + heartbeat,
    per-worker liveness/respawn/request slabs.  One anonymous shared
    mapping, created pre-fork; every field has exactly one writer, so
    reads are lock-free."""

    def __init__(self, nworkers: int):
        self.nworkers = int(nworkers)
        self._mm = mmap.mmap(-1, (_GHDR + self.nworkers * _WSLOTS) * 8)
        self._a = np.frombuffer(self._mm, dtype=np.int64)
        self._a[4] = self.nworkers

    def _w(self, idx: int) -> int:
        return _GHDR + int(idx) * _WSLOTS

    # owner ------------------------------------------------------------------

    def bump_owner_gen(self) -> int:
        self._a[0] += 1
        return int(self._a[0])

    def owner_gen(self) -> int:
        return int(self._a[0])

    def owner_register(self, pid: int) -> None:
        self._a[1] = pid
        self._a[2] = _now_ns()

    def owner_beat(self, co_stats: dict | None = None) -> None:
        if co_stats:
            self._a[5] = int(co_stats.get("dispatches", 0))
            self._a[6] = int(co_stats.get("items", 0))
            self._a[7] = int(co_stats.get("pending_items", 0))
            self._a[8] = int(co_stats.get("weight", 0))
        self._a[2] = _now_ns()

    def owner_ok(self, stale_s: float) -> bool:
        if not self._a[1]:
            return False
        return (_now_ns() - int(self._a[2])) < int(stale_s * 1e9)

    # device -----------------------------------------------------------------

    def publish_device(self, info: dict) -> None:
        """Owner: what ops/devices found.  The count is written last —
        it is the field readers poll."""
        self._mm[12 * 8:13 * 8] = info["platform"].encode(
            "ascii", "replace")[:8].ljust(8, b"\0")
        self._mm[13 * 8:16 * 8] = info["kind"].encode(
            "ascii", "replace")[:24].ljust(24, b"\0")
        self._a[10] = int(info["count"])

    def device_info(self) -> dict | None:
        """The owner's answer, or None before it gave one."""
        count = int(self._a[10])
        if not count:
            return None
        return {
            "platform": bytes(self._mm[12 * 8:13 * 8]).rstrip(
                b"\0").decode("ascii"),
            "kind": bytes(self._mm[13 * 8:16 * 8]).rstrip(
                b"\0").decode("ascii"),
            "count": count,
        }

    # topology ---------------------------------------------------------------

    def bump_topology_gen(self) -> int:
        """Pool-topology epoch: bumped by whichever worker serves an
        admin pool/add or pool/decommission call after it persisted
        pool-topology.json; every worker polls it in the idle loop and
        folds the delta into its own engine stack (see
        server/topology.py)."""
        self._a[9] += 1
        return int(self._a[9])

    def topology_gen(self) -> int:
        return int(self._a[9])

    def owner_info(self) -> dict:
        d = int(self._a[5])
        return {
            "role": "owner", "pid": int(self._a[1]),
            "generation": int(self._a[0]),
            "up": self.owner_ok(_stale_s()),
            "co_dispatches": d, "co_items": int(self._a[6]),
            "co_pending_items": int(self._a[7]),
            "co_occupancy": (int(self._a[6]) / d) if d else 0.0,
        }

    # workers ----------------------------------------------------------------

    def worker_register(self, idx: int, pid: int) -> None:
        w = self._w(idx)
        self._a[w + 0] = pid
        self._a[w + 1] = _now_ns()
        self._a[w + 2] = 0          # ready
        self._a[w + 3] = 0          # draining

    def worker_beat(self, idx: int, inflight: int = 0) -> None:
        w = self._w(idx)
        self._a[w + 1] = _now_ns()
        self._a[w + 6] = int(inflight)

    def set_ready(self, idx: int) -> None:
        self._a[self._w(idx) + 2] = 1

    def is_ready(self, idx: int) -> bool:
        return bool(self._a[self._w(idx) + 2])

    def set_draining(self, idx: int) -> None:
        self._a[self._w(idx) + 3] = 1

    def bump_respawn(self, idx: int) -> int:
        w = self._w(idx)
        self._a[w + 4] += 1
        return int(self._a[w + 4])

    def note_request(self, idx: int) -> None:
        self._a[self._w(idx) + 5] += 1

    def set_audit_dropped(self, idx: int, n: int) -> None:
        """This worker's cumulative audit-entry shed count (the writer
        is the worker itself — single-writer discipline like the rest
        of the slab)."""
        self._a[self._w(idx) + 7] = int(n)

    def note_hotcache(self, idx: int, hit: bool) -> None:
        """Per-worker hot-tier hit/miss tally (the cache segment is
        shared, so per-worker counters are the only way to see that
        worker B is hitting on worker A's fills)."""
        self._a[self._w(idx) + (8 if hit else 9)] += 1

    def worker_rows(self) -> list[dict]:
        stale = int(_stale_s() * 1e9)
        now = _now_ns()
        rows = []
        for i in range(self.nworkers):
            w = self._w(i)
            rows.append({
                "worker": i,
                "pid": int(self._a[w + 0]),
                "up": bool(self._a[w + 0])
                      and (now - int(self._a[w + 1])) < stale,
                "ready": bool(self._a[w + 2]),
                "draining": bool(self._a[w + 3]),
                "respawns": int(self._a[w + 4]),
                "requests": int(self._a[w + 5]),
                "inflight": int(self._a[w + 6]),
                "audit_dropped": int(self._a[w + 7]),
                "hotcache_hits": int(self._a[w + 8]),
                "hotcache_misses": int(self._a[w + 9]),
            })
        return rows


class WorkerPlane:
    """Everything the pool shares, created by the supervisor BEFORE any
    fork: the control block, the shard arena, the request ring into the
    owner, and one response ring per worker.  Also the duck type
    ops/ipc_dispatch.py talks to (arena / req_ring / resp_rings /
    owner_ok / owner_gen)."""

    def __init__(self, nworkers: int, arena_bytes: int | None = None,
                 ring_capacity: int | None = None):
        self.nworkers = int(nworkers)
        if ring_capacity is None:
            try:
                ring_capacity = int(os.environ.get(
                    "MTPU_IPC_RING", "512") or 512)
            except ValueError:
                ring_capacity = 512
        self.state = SharedState(self.nworkers)
        self.arena = ShmArena(arena_bytes or default_arena_bytes())
        self.req_ring = ShmRing(ring_capacity)
        self.resp_rings = [ShmRing(ring_capacity)
                           for _ in range(self.nworkers)]
        # The pool-shared hot-object tier: the cache segment MUST exist
        # before the first fork so every worker inherits the SAME
        # mapping — worker A's fill is worker B's hit (engine/hotcache
        # is import-light: stdlib + numpy + ops.shm_arena, no jax).
        from ..engine.hotcache import maybe_tier
        self.hotcache = maybe_tier()
        # The overload plane's admission slab likewise MUST exist
        # before the first fork: MTPU_WORKERS=N enforces ONE global
        # requests-max cap and one pressure signal, not N local ones.
        # get_plane() installs the module singleton, so every forked
        # worker's S3Server picks up this same mapping.
        from . import qos as _qos
        self.qos = _qos.get_plane(nworkers=self.nworkers)

    def owner_ok(self) -> bool:
        return self.state.owner_ok(_stale_s())

    def owner_gen(self) -> int:
        return self.state.owner_gen()

    # -- observability -------------------------------------------------------

    def workers_info(self) -> dict:
        return {
            "workers": self.state.worker_rows(),
            "owner": self.state.owner_info(),
            "arena": self.arena.stats(),
            "rings": {"request_depth": self.req_ring.depth(),
                      "response_depths": [r.depth()
                                          for r in self.resp_rings]},
            "hotcache": (self.hotcache.stats()
                         if self.hotcache is not None else None),
            "qos": self.qos.stats(),
        }

    def render_prom(self) -> str:
        """Prometheus families for the pool plane — appended to EVERY
        worker's /metrics render, so any worker the balancer lands on
        exports the aggregate view (the slabs live in shared memory)."""
        out = []

        def fam(name, help_, rows):
            out.append(f"# HELP {name} {help_}")
            out.append(f"# TYPE {name} gauge")
            for labels, v in rows:
                lab = ",".join(f'{k}="{v2}"' for k, v2 in labels.items())
                out.append(f"{name}{{{lab}}} {v}"
                           if lab else f"{name} {v}")

        rows = self.state.worker_rows()
        fam("mtpu_worker_up", "Worker heartbeat is fresh",
            [({"worker": r["worker"]}, int(r["up"])) for r in rows])
        fam("mtpu_worker_draining", "Worker is draining",
            [({"worker": r["worker"]}, int(r["draining"]))
             for r in rows])
        fam("mtpu_worker_respawns_total",
            "Times the supervisor respawned this worker slot",
            [({"worker": r["worker"]}, r["respawns"]) for r in rows])
        fam("mtpu_worker_requests_total",
            "HTTP requests handled by this worker",
            [({"worker": r["worker"]}, r["requests"]) for r in rows])
        fam("mtpu_worker_inflight_requests",
            "Requests currently inflight in this worker",
            [({"worker": r["worker"]}, r["inflight"]) for r in rows])
        fam("mtpu_worker_audit_dropped_total",
            "Audit entries shed by this worker's targets",
            [({"worker": r["worker"]}, r["audit_dropped"])
             for r in rows])
        # Per-worker view of the SHARED hot tier (aggregate cache
        # counters export via the registry's mtpu_hotcache_* families;
        # distinct names avoid duplicate-family renders in pool mode).
        fam("mtpu_worker_hotcache_hits_total",
            "Hot-object cache hits served by this worker",
            [({"worker": r["worker"]}, r["hotcache_hits"])
             for r in rows])
        fam("mtpu_worker_hotcache_misses_total",
            "Hot-object cache misses seen by this worker",
            [({"worker": r["worker"]}, r["hotcache_misses"])
             for r in rows])
        oi = self.state.owner_info()
        fam("mtpu_owner_up", "Device-owner heartbeat is fresh",
            [({}, int(oi["up"]))])
        fam("mtpu_owner_generation", "Device-owner respawn generation",
            [({}, oi["generation"])])
        fam("mtpu_owner_coalesce_occupancy",
            "Mean items per owner-side coalesced dispatch",
            [({}, round(oi["co_occupancy"], 4))])
        fam("mtpu_owner_coalesce_pending_items",
            "Items queued in the owner's coalescer",
            [({}, oi["co_pending_items"])])
        a = self.arena.stats()
        fam("mtpu_shm_arena_bytes", "Dispatch arena capacity",
            [({}, a["arena_bytes"])])
        fam("mtpu_shm_arena_in_use_bytes", "Dispatch arena occupancy",
            [({}, a["in_use_bytes"])])
        fam("mtpu_shm_arena_high_water_bytes",
            "Dispatch arena high-water occupancy",
            [({}, a["high_water_bytes"])])
        fam("mtpu_shm_arena_alloc_waits_total",
            "Arena allocations that had to wait (backpressure)",
            [({}, a["alloc_waits"])])
        fam("mtpu_shm_arena_alloc_timeouts_total",
            "Arena allocations that timed out (caller degraded local)",
            [({}, a["alloc_timeouts"])])
        fam("mtpu_ipc_ring_depth", "Dispatch ring queue depth",
            [({"ring": "request"}, self.req_ring.depth())]
            + [({"ring": f"response{i}"}, r.depth())
               for i, r in enumerate(self.resp_rings)])
        return "\n".join(out) + "\n"


# -- child process mains ------------------------------------------------------

#: set by the provisional child signal handler when a TERM/INT lands
#: during boot, BEFORE the child's real handler exists.  Without this,
#: the handler inherited from the supervisor's fork would swallow the
#: drain fan-out into the supervisor's (copied) stopping dict and a
#: still-booting worker would serve forever.
_early_stop = {"hit": False}


def _provisional_sig(signum, frame):
    _early_stop["hit"] = True


def _child_entry(fn, *a) -> None:
    """Run a forked child's main; any escape is a crash, not a return
    into the supervisor's stack."""
    signal.signal(signal.SIGTERM, _provisional_sig)
    signal.signal(signal.SIGINT, _provisional_sig)
    try:
        rc = fn(*a)
    except SystemExit as e:
        rc = int(e.code or 0)
    except BaseException:  # noqa: BLE001 — show the child's death
        import traceback
        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc & 0xFF)


def _owner_ladders(cfg: dict) -> None:
    """The shape ladders of the pool's boot geometry, in the one
    process that holds the devices: each pool's sets at their default
    parity, set i on lane i % lanes (what the workers' ErasureSets
    submit under).  A storage class set later in a worker is not seen
    here: its batches run at the top step."""
    from ..engine.erasure_set import BATCH_BLOCKS, BLOCK_SIZE
    from ..ops import coalesce, devices
    from ..storage.bitrot_io import write_algo
    if not devices.on_tpu():
        return
    for paths in cfg["pool_paths"]:
        n = cfg["set_drive_count"] or len(paths)
        k = n - n // 2
        for lane in {i % devices.n_devices()
                     for i in range(len(paths) // n)}:
            coalesce.build_geometry_ladder(
                k, n // 2, -(-BLOCK_SIZE // k), write_algo(),
                BATCH_BLOCKS, lane, padded_blocks=BLOCK_SIZE % k != 0)


def _owner_main(plane: WorkerPlane, cfg: dict) -> int:
    _set_pdeathsig()
    os.environ["MTPU_WORKER_ROLE"] = "owner"
    # The owner IS the remote end — it must never try to remote-submit.
    os.environ["MTPU_IPC_DISPATCH"] = "0"
    plane.state.owner_register(os.getpid())

    stop = threading.Event()

    def _sig(signum, frame):
        stop.set()
    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)
    if _early_stop["hit"]:       # TERM landed during import/boot
        stop.set()

    # The chip's one process: ask JAX (a backend that cannot start is
    # an error and takes the pool down with it), prove every lane, then
    # tell the workers what they are serving from.
    from ..ops import coalesce, devices, ipc_dispatch
    from ..ops.selftest import device_lane_self_test
    device_lane_self_test()
    info = devices.describe()
    plane.state.publish_device(info)
    if plane.state.owner_gen() == 1:
        print(devices.boot_line(info) + f" (device owner, pid "
              f"{os.getpid()})", flush=True)
    co = coalesce.get()
    ipc_dispatch.serve_owner(plane, stop, co)
    _owner_ladders(cfg)
    # Heartbeat on the main thread: workers route remote only while
    # this stays fresh, so a wedged owner quietly degrades the pool to
    # local dispatch instead of hanging it.
    while not stop.wait(0.2):
        plane.state.owner_beat(co.stats())
    co.close()
    return 0


def _worker_main(plane: WorkerPlane, idx: int, cfg: dict) -> int:
    _set_pdeathsig()
    os.environ["MTPU_WORKER_ID"] = str(idx)
    os.environ["MTPU_WORKERS_TOTAL"] = str(plane.nworkers)
    os.environ["MTPU_WORKER_ROLE"] = "worker"
    # No backend of that name exists, so JAX raises if anything in this
    # process reaches for one: the devices are the owner's.
    os.environ["JAX_PLATFORMS"] = "the_device_owner_holds_the_devices"
    from ..ops import devices
    info = plane.state.device_info()     # run_pool waited for it
    devices.adopt(info["platform"], info["kind"], info["count"])
    if idx != 0:
        # Exactly one scanner / recovery owner per deployment.
        os.environ["MTPU_SCANNER"] = "0"
    plane.state.worker_register(idx, os.getpid())

    # A respawned worker inherits its predecessor's response ring;
    # drain stale descriptors and return their arena slots.
    from ..ops import ipc_dispatch as ipcmod
    for rec in plane.resp_rings[idx].drain():
        try:
            (_, _, _, off, total, _, status,
             _, _) = ipcmod._DESC.unpack(rec[:ipcmod._DESC.size])
            if total and status != ipcmod.ST_DROP:
                plane.arena.free(off, total)
        except Exception:  # noqa: BLE001 — torn record
            pass

    if idx == 0:
        from ..ops.selftest import run_startup_self_tests
        run_startup_self_tests(device=False)

    from ..background.mrf import attach_mrf
    from ..engine.pools import ServerPools
    from ..engine.sets import ErasureSets
    from ..storage.drive import LocalDrive
    from ..storage.health_wrap import wrap_drives
    from ..storage.recovery import boot_recovery_sweep

    # A respawned worker must come back with the LIVE topology (pools
    # added via admin pool/add), not the boot-time flags: the persisted
    # pool-topology.json wins when present.
    from . import topology as topo_mod
    topo = topo_mod.load_topology_from_root(cfg["pool_paths"][0][0])
    pool_specs = ([(p["paths"], p.get("set_drive_count")
                    or cfg["set_drive_count"]) for p in topo["pools"]]
                  if topo else
                  [(paths, cfg["set_drive_count"])
                   for paths in cfg["pool_paths"]])
    pool_sets: list[ErasureSets] = []
    for paths, sdc in pool_specs:
        local = [LocalDrive(p) for p in paths]
        if idx == 0:
            boot_recovery_sweep(local)
        pool_sets.append(ErasureSets(
            wrap_drives(local),
            set_drive_count=sdc or len(local),
            deployment_id=(pool_sets[0].deployment_id
                           if pool_sets else None)))
    pools = ServerPools(pool_sets)
    mrf_queues = attach_mrf(pools)
    if plane.hotcache is not None:
        # Attach the pre-fork cache segment this worker inherited;
        # hits/misses also land in this worker's slab slots so the
        # pool exposes per-worker ratios over the ONE shared cache.
        from ..engine.hotcache import attach_pools as attach_hotcache
        if attach_hotcache(pools, plane.hotcache) is not None:
            plane.hotcache.on_lookup = (
                lambda hit, _i=idx: plane.state.note_hotcache(_i, hit))
    if topo:
        pools.draining |= {int(i) for i in topo.get("draining", [])
                           if 0 <= int(i) < len(pools.pools)}
        topo_mod.refresh_relocations(pools)
    topo_seen = plane.state.topology_gen()
    if idx == 0:
        # Recovery owner: relaunch drains interrupted by the last death
        # (the decom journal's state survives kill -9 at `draining`).
        from ..background.decom import resume_decommissions
        for d in resume_decommissions(pools):
            print(f"minio_tpu: worker 0 resumed decommission of pool "
                  f"{d.pool_idx} ({d.state})", flush=True)

    from ..background.scanner import DataScanner
    from ..bucket.notify import NotificationSystem
    from ..bucket.replication import ReplicationPool
    from ..iam.iam import IAMSys
    iam = IAMSys(pools)
    replication = ReplicationPool(pools)
    scanner = (DataScanner(pools).start()
               if idx == 0
               and os.environ.get("MTPU_SCANNER", "1") != "0" else None)

    # The cross-process coalescer front end: engine call sites keep
    # doing `coalesce.get()`; remote-eligible keys now ship to the
    # device owner, the rest stay on this worker's local scheduler.
    from ..ops import coalesce
    coalesce.attach_remote(
        ipcmod.RemoteCoalescer(plane, idx))

    from .server import S3Server
    srv = S3Server(pools, cfg["creds"], host=cfg["host"],
                   port=cfg["port"], iam=iam, scanner=scanner,
                   notify=NotificationSystem(), replication=replication,
                   certs=cfg["certs"], reuse_port=True,
                   worker_plane=plane, worker_id=idx).start()

    stop = threading.Event()

    def _sig(signum, frame):
        # Idempotent on purpose: the supervisor re-sends TERM while
        # stopping (to cover the boot window) and owns the force path
        # (its own second signal SIGKILLs the pool).
        stop.set()
    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)
    if _early_stop["hit"]:       # TERM landed during the heavy boot
        stop.set()

    def _beat():
        while True:
            plane.state.worker_beat(idx, inflight=srv._inflight)
            time.sleep(0.4)
    threading.Thread(target=_beat, name="mtpu-worker-beat",
                     daemon=True).start()

    plane.state.set_ready(idx)
    if idx == 0:
        print(f"minio_tpu worker pool serving on {srv.endpoint} "
              f"({plane.nworkers} workers, SO_REUSEPORT)", flush=True)
    reloc_beat = 0
    while not stop.wait(timeout=0.5):
        if srv.service_event:
            # Admin restart/stop reaches ONE worker; exit and let the
            # supervisor respawn this slot fresh (restart) — pool-wide
            # stop is the supervisor's SIGTERM, not this path.
            break
        gen = plane.state.topology_gen()
        if gen != topo_seen:
            # Another worker changed the pool topology (pool/add or a
            # decommission state flip): fold the persisted delta in.
            topo_seen = gen
            try:
                topo_mod.adopt_topology(pools)
            except Exception as e:  # noqa: BLE001 — stay serving
                print(f"minio_tpu: worker {idx} topology adopt "
                      f"failed: {e}", file=sys.stderr, flush=True)
        elif pools.draining:
            # An active drain relocates multipart uploads continuously;
            # a part PUT can land on ANY worker, so the relocation map
            # must track the mover's journal, not just topology bumps.
            reloc_beat += 1
            if reloc_beat % 4 == 0:
                try:
                    topo_mod.refresh_relocations(pools)
                except Exception:  # noqa: BLE001
                    pass
    plane.state.set_draining(idx)
    srv.drain()
    srv.shutdown()
    if scanner is not None:
        scanner.stop()
    for q in mrf_queues:
        q.stop()
    coalesce.detach_remote()
    return 0


# -- supervisor ---------------------------------------------------------------

def _reserve_port(host: str, port: int) -> tuple[socket.socket, int]:
    """Bind a REUSEPORT placeholder so `--port 0` resolves to ONE
    ephemeral port every worker can share; kept open for the pool's
    lifetime so the port cannot be reused by somebody else between
    worker respawns."""
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    try:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
    except (AttributeError, OSError):
        s.close()
        raise RuntimeError(
            "MTPU_WORKERS>0 requires SO_REUSEPORT support") from None
    s.bind((host, port))
    return s, s.getsockname()[1]


def _fork(fn, *a) -> int:
    pid = os.fork()
    if pid == 0:
        _child_entry(fn, *a)        # never returns
    return pid


def run_pool(nworkers: int, pool_paths: list[list[str]], creds,
             host: str, port: int, set_drive_count: int | None,
             certs: tuple[str, str] | None) -> int:
    """Supervise the pool until signalled.  The supervisor stays
    import-light (no jax, no engine): all heavy state is built inside
    the forked children, AFTER the shared plane exists."""
    import faulthandler
    faulthandler.register(signal.SIGUSR2, all_threads=True)
    plane = WorkerPlane(nworkers)
    plane.state._a[3] = os.getpid()
    reserve, port = _reserve_port(host, port)
    cfg = {"pool_paths": pool_paths, "creds": creds, "host": host,
           "port": port, "set_drive_count": set_drive_count,
           "certs": certs}

    stopping = {"flag": False, "force": False}

    def _sig(signum, frame):
        if stopping["flag"]:
            stopping["force"] = True
            return
        stopping["flag"] = True
    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)

    children: dict[int, tuple[str, int]] = {}   # pid -> (role, idx)

    plane.state.bump_owner_gen()
    owner = _fork(_owner_main, plane, cfg)
    children[owner] = ("owner", -1)
    deadline = time.monotonic() + float(
        os.environ.get("MTPU_BOOT_TIMEOUT", "120") or 120)

    def boot_wait(done, pid_watched: int, who: str) -> int:
        """0 once done(); else the exit code to leave with (the pool is
        already killed)."""
        while not done():
            pid, st = os.waitpid(-1, os.WNOHANG)
            if pid == pid_watched:
                rc = os.waitstatus_to_exitcode(st)
                print(f"minio_tpu: {who} died during boot (rc={rc})",
                      file=sys.stderr, flush=True)
                _killall(children, signal.SIGKILL)
                return rc if rc > 0 else 1
            if stopping["flag"] or time.monotonic() > deadline:
                _killall(children, signal.SIGKILL)
                return 1
            time.sleep(0.05)
        return 0

    # The owner answers first: no worker exists before the pool knows
    # what it computes on, and an owner whose JAX cannot start (no chip
    # under JAX_PLATFORMS=tpu) ends the pool here.
    rc = boot_wait(lambda: plane.state.device_info() is not None,
                   owner, "device owner")
    if rc:
        return rc

    # Worker 0 boots ALONE next: it creates/adopts format.json, runs
    # the recovery sweep and MRF adoption — the writes every other
    # worker must observe, not race.
    w0 = _fork(_worker_main, plane, 0, cfg)
    children[w0] = ("worker", 0)
    rc = boot_wait(lambda: plane.state.is_ready(0), w0, "worker 0")
    if rc:
        return rc

    for i in range(1, nworkers):
        children[_fork(_worker_main, plane, i, cfg)] = ("worker", i)

    crash_armed = bool(os.environ.get("MTPU_CRASH"))
    termed = 0.0
    owner_termed = False
    rc_final = 0
    while children:
        if stopping["force"]:
            _killall(children, signal.SIGKILL)
            for pid in list(children):
                _reap(pid)
            return 130
        if stopping["flag"] and time.monotonic() - termed > 1.0:
            # Drain fan-out: workers first; the owner keeps the
            # dispatch plane alive while their inflight finishes.
            # Re-sent every second: a child mid-boot parks an early
            # TERM in its provisional handler, and repeats are free
            # (the real handler's first set() wins, seconds force).
            termed = time.monotonic()
            for pid, (role, _) in children.items():
                if role == "worker":
                    _kill(pid, signal.SIGTERM)
        if termed and not owner_termed and not any(
                role == "worker" for role, _ in children.values()):
            owner_termed = True
            for pid, (role, _) in children.items():
                if role == "owner":
                    _kill(pid, signal.SIGTERM)
        try:
            pid, st = os.waitpid(-1, os.WNOHANG)
        except ChildProcessError:
            break
        if pid == 0:
            time.sleep(0.1)
            continue
        role, idx = children.pop(pid, ("?", -1))
        rc = os.waitstatus_to_exitcode(st)
        if stopping["flag"]:
            if role == "worker" and rc not in (0, 143):
                rc_final = rc_final or (rc if rc > 0 else 1)
            continue
        if crash_armed and rc == 137:
            # A kill-matrix crash point fired inside this child: the
            # whole pool IS the server under test — propagate.
            _killall(children, signal.SIGKILL)
            for p in list(children):
                _reap(p)
            return 137
        delay = _respawn_delay_s()
        if delay:
            time.sleep(delay)
        if role == "owner":
            print(f"minio_tpu: device owner died (rc={rc}); "
                  f"respawning", file=sys.stderr, flush=True)
            plane.state.bump_owner_gen()
            children[_fork(_owner_main, plane, cfg)] = ("owner", -1)
        elif role == "worker":
            n = plane.state.bump_respawn(idx)
            print(f"minio_tpu: worker {idx} died (rc={rc}); "
                  f"respawn #{n}", file=sys.stderr, flush=True)
            children[_fork(_worker_main, plane, idx, cfg)] = \
                ("worker", idx)
    try:
        reserve.close()
    except OSError:
        pass
    return rc_final


def _kill(pid: int, sig: int) -> None:
    try:
        os.kill(pid, sig)
    except (ProcessLookupError, PermissionError):
        pass


def _killall(children: dict, sig: int) -> None:
    for pid in children:
        _kill(pid, sig)


def _reap(pid: int) -> None:
    try:
        os.waitpid(pid, 0)
    except (ChildProcessError, InterruptedError):
        pass


__all__ = ["SharedState", "WorkerPlane", "nworkers_env", "run_pool"]
