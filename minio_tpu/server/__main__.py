"""CLI entry: `python -m minio_tpu.server --drives /tmp/d{1...4} --port 9001`.

The serverMain equivalent (/root/reference/cmd/server-main.go:441): expand
drive endpoints, run startup self-tests, build the object layer
(pools -> sets -> drives), start the S3 front door, serve until signalled.
Credentials come from MTPU_ROOT_USER / MTPU_ROOT_PASSWORD (the reference's
MINIO_ROOT_USER convention), defaulting to minioadmin/minioadmin.
"""

from __future__ import annotations

import argparse
import os
import signal
import sys


def expand_ellipses(pattern: str) -> list[str]:
    """Expand `/tmp/d{1...4}` patterns
    (cf. cmd/endpoint-ellipses.go:341)."""
    from ..topology.endpoints import expand_one, has_ellipses
    if has_ellipses(pattern):
        return expand_one(pattern)
    return pattern.split()


def bucket_dns_from_env(host: str, port: int):
    """Federation wiring (the reference's MINIO_ETCD_ENDPOINTS +
    MINIO_DOMAIN convention): MTPU_ETCD_ENDPOINTS=host:port and
    MTPU_DOMAIN=cluster.domain enable bucket-DNS federation; absent ->
    standalone namespace (cf. cmd/etcd.go + internal/config/dns)."""
    ep = os.environ.get("MTPU_ETCD_ENDPOINTS", "")
    domain = os.environ.get("MTPU_DOMAIN", "")
    if not ep or not domain:
        return None
    from ..bucket.event_targets import _hostport
    from ..cluster.federation import BucketDNS, EtcdClient
    ehost, eport = _hostport(ep, 2379)   # handles http://, bare hosts
    try:
        return BucketDNS(EtcdClient(ehost, eport or 2379),
                         domain, host, port)
    except Exception as e:  # noqa: BLE001 — misconfig must be loud
        print(f"minio_tpu: federation config invalid: {e}",
              file=sys.stderr)
        raise SystemExit(2) from None


def parse_pool_paths(drive_groups: list[list[str]]) -> list[list[str]] | None:
    """Expand --drives groups into per-pool path lists; None on a
    mixed ellipsis/plain group (caller exits 2).

    Each --drives flag is one pool, and within a flag each
    space-separated ellipsis group is ALSO one pool — `--drives
    '/data{1...4} /newdata{1...4}'` is a two-pool deployment exactly
    like the reference's capacity-expansion syntax
    (cmd/endpoint-ellipses.go:341: one zone/pool per arg). Plain paths
    with no ellipses keep the legacy meaning: one pool over all."""
    from ..topology.endpoints import has_ellipses
    pool_paths: list[list[str]] = []
    for group in drive_groups:
        if len(group) > 1 and any(has_ellipses(a) for a in group):
            if not all(has_ellipses(a) for a in group):
                # The reference rejects mixed args too — a plain path
                # next to ellipsis pools would become a nonsensical
                # 1-drive pool.
                print("--drives: cannot mix ellipsis pool patterns "
                      f"with plain paths in one group: {group}",
                      file=sys.stderr)
                return None
            pool_paths.extend(expand_ellipses(a) for a in group)
        else:
            pool_paths.append(
                [p for a in group for p in expand_ellipses(a)])
    return pool_paths


def install_signal_handlers(stop) -> None:
    """SIGTERM and SIGINT both start a graceful drain (cmd/signals.go:
    the reference treats them identically); a SECOND signal of either
    kind forces immediate exit — the escape hatch when a drain hangs."""
    def _sig(signum, frame):
        if stop.is_set():
            try:
                os.write(2, b"minio_tpu: second signal, forcing exit\n")
            except OSError:
                pass
            os._exit(130 if signum == signal.SIGINT else 143)
        stop.set()
    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="minio_tpu.server")
    ap.add_argument("--drives", required=False, action="append",
                    default=None,
                    help="drive paths, ellipses ok: /tmp/d{1...4}; "
                         "repeat the flag to add a POOL (capacity "
                         "expansion) — each --drives is one pool")
    ap.add_argument("--port", type=int, default=9000)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--set-drive-count", type=int, default=None)
    ap.add_argument("--certs-dir",
                    default=os.environ.get("MTPU_CERTS_DIR", ""),
                    help="dir with public.crt/private.key -> serve HTTPS")
    args = ap.parse_args(argv)

    from .sigv4 import Credentials

    creds = Credentials(os.environ.get("MTPU_ROOT_USER", "minioadmin"),
                        os.environ.get("MTPU_ROOT_PASSWORD", "minioadmin"))
    # Each --drives flag is one endpoint group; within a group, args
    # are space-separated (a node list in cluster mode, or ellipsis
    # pool groups standalone).  MTPU_POOLS is the flag-free spelling
    # (containers, harnesses): semicolon-separated pools, each a
    # space-separated ellipsis group — appended after any --drives.
    drive_flags = list(args.drives or [])
    env_pools = os.environ.get("MTPU_POOLS", "")
    if env_pools:
        drive_flags.extend(p for p in env_pools.split(";") if p.strip())
    if not drive_flags:
        print("minio_tpu: --drives (or MTPU_POOLS) required",
              file=sys.stderr)
        return 2
    drive_groups = [g.split() for g in drive_flags]
    endpoint_args = [a for g in drive_groups for a in g]
    cluster_mode = any("://" in a for a in endpoint_args)

    certs = None
    if args.certs_dir:
        cert = os.path.join(args.certs_dir, "public.crt")
        key = os.path.join(args.certs_dir, "private.key")
        if not (os.path.exists(cert) and os.path.exists(key)):
            print(f"--certs-dir: missing {cert} or {key}",
                  file=sys.stderr)
            return 2
        certs = (cert, key)

    # JAX's persistent compile cache: placed from outside where
    # JAX_COMPILATION_CACHE_DIR is set, else at a FIXED path in the
    # checkout (the path is part of the cache key, so one that moved
    # would never hit).  Set before the pre-fork branch and before any
    # jax import, so every child inherits it.  The served programs
    # compile in 0.2-2 s each, under jax's default 1 s floor for what
    # it keeps, hence the floor of 0.
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

    # Pre-fork worker pool (server/workers.py): MTPU_WORKERS=N forks N
    # SO_REUSEPORT HTTP workers plus one device-owner process.  The
    # branch sits BEFORE any engine/jax import — forking after XLA
    # spins up its thread pools is undefined behavior, so the
    # supervisor must stay light and each child builds its own stack.
    from .workers import nworkers_env
    nworkers = nworkers_env()
    if nworkers and cluster_mode:
        print("minio_tpu: MTPU_WORKERS ignored in cluster mode "
              "(one process per node)", file=sys.stderr, flush=True)
    elif nworkers:
        pool_paths = parse_pool_paths(drive_groups)
        if pool_paths is None:
            return 2
        from .workers import run_pool
        return run_pool(nworkers, pool_paths, creds, args.host,
                        args.port, args.set_drive_count, certs)

    # Startup self-test guards (hard-fail like cmd/erasure-coding.go:158,
    # cmd/bitrot.go:214).
    from ..ops import devices
    from ..ops.selftest import run_startup_self_tests
    run_startup_self_tests()
    # This process initialised the JAX backend: say, once, what every
    # device decision below it was made from.
    print(devices.boot_line(), flush=True)

    from .server import S3Server

    if cluster_mode:
        # Distributed boot: URL endpoints, every node launched with the
        # same list (cf. serverMain distributed path,
        # cmd/server-main.go:441). The front door starts first; S3
        # serves 503 until format quorum + peer verify complete.
        from .cluster import boot_cluster_node

        if certs is not None and not all(
                a.startswith("https://") for a in endpoint_args):
            # TLS without https endpoints would serve the planes over
            # TLS while peers dial plaintext — fail loudly, don't
            # silently downgrade either side.
            print("--certs-dir requires https:// cluster endpoints",
                  file=sys.stderr)
            return 2
        if certs is None and any(a.startswith("https://")
                                 for a in endpoint_args):
            print("https:// endpoints require --certs-dir",
                  file=sys.stderr)
            return 2

        from ..bucket.notify import NotificationSystem

        def factory(node):
            srv = S3Server(None, creds, host=args.host, port=args.port,
                           rpc_router=node.router, certs=certs,
                           notify=NotificationSystem(),
                           bucket_dns=bucket_dns_from_env(
                               args.host, args.port)).start()
            print(f"minio_tpu cluster node on {srv.endpoint} "
                  f"(first={node.is_first}, "
                  f"{len(node.local_drives)} local / "
                  f"{len(node.endpoints)} total drives, "
                  f"set={node.set_drive_count}) — waiting for cluster",
                  flush=True)
            return srv

        import threading
        stop = threading.Event()
        install_signal_handlers(stop)
        while True:
            try:
                node, srv0, pools = boot_cluster_node(
                    drive_groups if len(drive_groups) > 1
                    else endpoint_args,
                    args.host, args.port, creds,
                    set_drive_count=args.set_drive_count,
                    server_factory=factory, certs_dir=args.certs_dir,
                    timeout=float(os.environ.get("MTPU_BOOT_TIMEOUT",
                                                 "120")))
            except Exception as e:  # noqa: BLE001
                print(f"minio_tpu: cluster boot failed: {e}",
                      file=sys.stderr, flush=True)
                return 1
            srv0.build_ladders(hold_ready=True)
            print(f"minio_tpu cluster node ready on {srv0.endpoint} "
                  f"(deployment ok)", flush=True)
            try:
                while not stop.wait(timeout=1.0):
                    if srv0.service_event:
                        break
            except KeyboardInterrupt:
                break
            if srv0.service_event == "restart" and not stop.is_set():
                # Full re-boot: tear down, rejoin the cluster (format
                # adopt + peer verify run again), same as the
                # standalone restart loop. Each boot builds a fresh
                # scanner; stop the outgoing one.
                print("minio_tpu: service restart requested", flush=True)
                srv0.shutdown()
                if srv0.scanner is not None:
                    srv0.scanner.stop()
                node.close()
                continue
            break
        # Cluster stop path: same drain as standalone — inflight
        # requests finish, heal/MRF checkpoint, then the node leaves.
        srv0.drain()
        srv0.shutdown()
        if srv0.scanner is not None:
            srv0.scanner.stop()
        node.close()
        return 0

    from ..engine.pools import ServerPools
    from ..engine.sets import ErasureSets
    from ..storage.drive import LocalDrive

    pool_paths = parse_pool_paths(drive_groups)
    if pool_paths is None:
        return 2
    from ..background.mrf import attach_mrf
    from ..storage.health_wrap import wrap_drives

    from ..storage.recovery import boot_recovery_sweep

    pool_sets: list[ErasureSets] = []
    swept = {"drives": 0, "tmp_entries": 0, "mp_stage": 0}
    for paths in pool_paths:
        # Health wrap at boot: per-API latency/error stats plus the
        # drive circuit breaker (ok -> suspect -> offline + background
        # probe), the xl-storage-disk-id-check.go:68 layering.
        local = [LocalDrive(p) for p in paths]
        # Boot-time recovery sweep BEFORE the engine takes traffic:
        # stale tmp/trash from the previous epoch, orphaned multipart
        # staging (cmd/prepare-storage.go role).
        rec = boot_recovery_sweep(local)
        for key in swept:
            swept[key] += rec[key]
        drives = wrap_drives(local)
        pool_sets.append(ErasureSets(
            drives,
            set_drive_count=args.set_drive_count or len(drives),
            deployment_id=(pool_sets[0].deployment_id
                           if pool_sets else None)))
    pools = ServerPools(pool_sets)
    if swept["tmp_entries"] or swept["mp_stage"]:
        print(f"minio_tpu: recovery sweep: {swept['tmp_entries']} stale "
              f"tmp entr(ies), {swept['mp_stage']} orphaned multipart "
              f"staging file(s) across {swept['drives']} drive(s)",
              flush=True)
    # MRF heal queues: writes that missed a breaker-offline drive heal
    # back to full width as soon as the drive recovers.  Journaled to
    # each pool's first drive so pending heals survive restarts.
    mrf_queues = attach_mrf(pools)
    replayed = sum(q.replayed for q in mrf_queues)
    if replayed:
        print(f"minio_tpu: MRF journal: replayed {replayed} pending "
              f"heal(s)", flush=True)
    # RAM hot-object tier (single-process: one private segment; the
    # pool path builds it pre-fork in WorkerPlane instead).
    from ..engine.hotcache import attach_pools as attach_hotcache
    if attach_hotcache(pools) is not None:
        print("minio_tpu: hot-object cache: "
              f"{pools.hot_tier.stats()['segment_bytes'] >> 20} MiB "
              "segment attached", flush=True)
    # Live-added pools survive a restart with stale --drives flags:
    # pool-topology.json (written by admin pool/add / decommission)
    # wins over the boot flags, and interrupted drains resume from
    # their journals — the kill-9 recovery path.
    from ..background.decom import resume_decommissions
    from .topology import adopt_topology
    adopted = adopt_topology(pools)
    if adopted:
        print(f"minio_tpu: topology: attached {adopted} live-added "
              f"pool(s)", flush=True)
    for d in resume_decommissions(pools):
        print(f"minio_tpu: resumed decommission of pool {d.pool_idx} "
              f"({d.state})", flush=True)

    # Full subsystem stack, the newAllSubsystems role
    # (cmd/server-main.go:441): IAM, scanner, notifications.
    from ..background.scanner import DataScanner
    from ..bucket.notify import NotificationSystem
    from ..bucket.replication import ReplicationPool
    from ..iam.iam import IAMSys
    iam = IAMSys(pools)
    # Replication journal replays BEFORE traffic — intents a kill-9
    # stranded re-enter the backlog here and drain once the persisted
    # bucket configs re-wire their targets.
    replication = ReplicationPool(pools)
    if replication.replayed:
        print(f"minio_tpu: replication journal: replayed "
              f"{replication.replayed} pending task(s)", flush=True)
    # Perpetual scanner lifecycle: an idle server crawls, accounts
    # usage, heals missing metadata, and bitrot-verifies every
    # deep_every-th cycle (cf. initDataScanner, cmd/server-main.go:441).
    # MTPU_SCANNER=0 disables it (deterministic-write harnesses: the
    # scanner's usage persistence writes through the same drive paths
    # the crash points instrument).
    scanner = (DataScanner(pools).start()
               if os.environ.get("MTPU_SCANNER", "1") != "0" else None)
    notify = NotificationSystem()
    # ILM/tiering plane: persisted tiers reload and the tier journal
    # replays BEFORE traffic — a kill-9 mid-transition resolves to
    # either the full hot version or a valid stub + tier object here.
    from ..bucket.tier import TierManager
    tier_mgr = TierManager(pools)
    replay = getattr(tier_mgr, "journal", None)
    if tier_mgr.counters.get("replayed"):
        print(f"minio_tpu: tier journal: replayed "
              f"{tier_mgr.counters['replayed']} record(s) "
              f"({tier_mgr.counters['orphans_reaped']} orphan(s) "
              f"reaped), {replay.pending() if replay else 0} pending",
              flush=True)

    import threading
    stop = threading.Event()
    install_signal_handlers(stop)
    port = args.port
    while True:
        srv = S3Server(pools, creds, host=args.host, port=port,
                       iam=iam, scanner=scanner, notify=notify,
                       replication=replication, certs=certs,
                       tier_mgr=tier_mgr,
                       bucket_dns=bucket_dns_from_env(args.host,
                                                      port)).start()
        port = srv.port                  # keep the port across restarts
        # The lanes' shape ladder for the geometry served, built off the
        # serving threads: until a step is built the next larger one
        # serves, so requests are answered meanwhile; only the
        # readiness probe waits for it.
        srv.build_ladders(hold_ready=True)
        if srv.bucket_dns is not None:
            # SRV records must advertise the BOUND port (--port 0
            # binds an ephemeral one)
            srv.bucket_dns.my_port = srv.port
        n_drives = sum(len(p) for p in pool_paths)
        desc = ", ".join(f"pool{i}: {len(p)} drives "
                         f"set={pool_sets[i].set_drive_count}"
                         for i, p in enumerate(pool_paths)) \
            if len(pool_paths) > 1 else \
            f"{n_drives} drives, set={pool_sets[0].set_drive_count}"
        print(f"minio_tpu server on {srv.endpoint} ({desc})",
              flush=True)
        try:
            # Event.wait is race-free against a signal arriving between
            # the check and the sleep (unlike signal.pause()); the admin
            # service endpoint shuts the listener down itself, flagged
            # via service_event.
            while not stop.wait(timeout=1.0):
                if srv.service_event:
                    break
        except KeyboardInterrupt:
            break
        if srv.service_event == "restart" and not stop.is_set():
            print("minio_tpu: service restart requested", flush=True)
            srv.service_event = ""
            # The admin handler schedules its own shutdown ~0.25 s out;
            # join it here so the port is released before rebinding
            # (shutdown is idempotent).
            srv.shutdown()
            continue             # scanner keeps running across restarts
        break
    # Graceful exit: drain (503 new requests, finish inflight, flush
    # digest lanes, checkpoint heal frontier + MRF journal), THEN drop
    # the listener and stop the background machinery.
    srv.drain()
    srv.shutdown()
    if scanner is not None:
        scanner.stop()
    for q in mrf_queues:
        q.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
