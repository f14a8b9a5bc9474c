"""Closed-loop load generator for the concurrent data plane.

Each client thread runs a closed loop (think wrk, not an open-loop
arrival process): issue one PUT or GET, wait for it, record the
latency, repeat — so `clients` IS the offered concurrency, which is
exactly the knob the dispatch coalescer packs across.  Results report
aggregate throughput, latency quantiles, and the coalescer's mean
batch occupancy over the run (from DATA_PATH snapshot deltas), the
three numbers the ISSUE's acceptance criteria compare at 1/4/16
clients.

Usable as a library (bench.py's concurrent suite) or a CLI:

    python tools/loadgen.py --clients 16 --size-kib 1024 \
        --mix 0.5 --duration 10 --root /tmp/lg

Two drive modes:

  * engine mode (default): clients call the ErasureSet directly — no
    HTTP, isolates the data plane.
  * HTTP mode (--endpoint http://...): clients speak SigV4 over the
    wire against a RUNNING server — the mode that can actually observe
    the pre-fork worker pool, since SO_REUSEPORT balancing happens at
    accept time.  --procs forks the CLIENT side into multiple
    processes too, so a GIL-bound load generator can't become the
    bottleneck while measuring a multi-process server.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from minio_tpu.observe.metrics import DATA_PATH  # noqa: E402
from minio_tpu.storage.drive import LocalDrive  # noqa: E402


def _quantile(lat_s: list[float], q: float) -> float:
    if not lat_s:
        return 0.0
    return float(np.quantile(np.asarray(lat_s), q))


def _proc_tree_cpu_s(pid: int) -> float | None:
    """user+sys CPU seconds of `pid` AND its descendants (the pre-fork
    worker pool) from /proc — the server-side bill an HTTP run can't
    get from its own rusage.  None when /proc is unreadable (non-Linux,
    process gone)."""
    try:
        tick = os.sysconf("SC_CLK_TCK")
    except (ValueError, OSError):
        return None

    def one(p: int) -> float:
        with open(f"/proc/{p}/stat", "rb") as f:
            # field 2 (comm) may contain spaces: split after ')'
            rest = f.read().rpartition(b")")[2].split()
        return (int(rest[11]) + int(rest[12])) / tick  # utime, stime

    def kids(p: int) -> list[int]:
        out: list[int] = []
        try:
            for task in os.listdir(f"/proc/{p}/task"):
                with open(f"/proc/{p}/task/{task}/children", "rb") as f:
                    out += [int(c) for c in f.read().split()]
        except OSError:
            pass
        return out

    try:
        total, queue, seen = 0.0, [pid], set()
        while queue:
            p = queue.pop()
            if p in seen:
                continue
            seen.add(p)
            try:
                total += one(p)
            except (OSError, IndexError, ValueError):
                continue
            queue += kids(p)
        return total
    except Exception:  # noqa: BLE001 — metrics-only, never break a run
        return None


def zipf_cdf(n: int, s: float) -> np.ndarray:
    """CDF of a Zipf(s) distribution over ranks 1..n: P(i) ∝ 1/i^s.
    Rank 0 is the hottest key.  Sampling = searchsorted(uniform) —
    O(log n) per draw, no rejection (np.random.zipf is unbounded)."""
    w = 1.0 / np.power(np.arange(1, n + 1, dtype=np.float64), s)
    return np.cumsum(w / w.sum())


def hot_rank_cut(n: int) -> int:
    """Ranks [0, cut) are the 'hot' class for the SLO split: the top
    decile (min 1 key) — under Zipf s≈1.1 it absorbs most GETs."""
    return max(1, n // 10)


def _zipf_pick(cdf: np.ndarray, crng) -> int:
    return int(np.searchsorted(cdf, crng.random(), side="right"))


def hot_cold_rows(lat_hot: list[float], lat_cold: list[float],
                  lat_ranged: list[float]) -> dict:
    """The SLO report rows the Zipfian runs compare: hot-key vs
    cold-key (vs ranged) p50/p99 — the hot rows are where a RAM hot
    tier must show up, the cold rows are where it must NOT regress."""
    return {
        "hot_gets": len(lat_hot),
        "hot_p50_ms": round(_quantile(lat_hot, 0.50) * 1e3, 3),
        "hot_p99_ms": round(_quantile(lat_hot, 0.99) * 1e3, 3),
        "cold_gets": len(lat_cold),
        "cold_p50_ms": round(_quantile(lat_cold, 0.50) * 1e3, 3),
        "cold_p99_ms": round(_quantile(lat_cold, 0.99) * 1e3, 3),
        "ranged_gets": len(lat_ranged),
        "ranged_p50_ms": round(_quantile(lat_ranged, 0.50) * 1e3, 3),
        "ranged_p99_ms": round(_quantile(lat_ranged, 0.99) * 1e3, 3),
    }


def keyspace_names(es, mode: str, total: int = 32,
                   prefix: str = "ks") -> list[str]:
    """Object names with PROVEN set placement (PR 10 device sharding):
    rejection-sample candidate names through the same sipHashMod the
    engine routes with.  'spread' returns names fanning out evenly over
    every erasure set (interleaved round-robin, so a client walking the
    list touches all sets — and therefore all device lanes —
    continuously); 'pinned' returns names that ALL land on set 0 (one
    lane saturated, the others idle).  A single ErasureSet has no ring,
    so both modes degrade to plain numbered names."""
    nset = int(getattr(es, "set_count", 1))
    key = getattr(es, "_dep_key", None)
    if nset <= 1 or key is None or mode == "default":
        return [f"{prefix}-{i}" for i in range(total)]
    from minio_tpu.utils.siphash import sip_hash_mod
    per: dict[int, list[str]] = {i: [] for i in range(nset)}
    want = max(1, total // nset) if mode == "spread" else total
    i = 0
    while True:
        if mode == "spread":
            if all(len(v) >= want for v in per.values()):
                break
        elif len(per[0]) >= want:
            break
        if i > 1_000_000:
            raise RuntimeError(f"keyspace sampling runaway ({mode})")
        name = f"{prefix}-{i}"
        i += 1
        per[sip_hash_mod(name, nset, key)].append(name)
    if mode == "pinned":
        return per[0][:want]
    if mode != "spread":
        raise ValueError(f"unknown keyspace mode {mode!r}")
    return [per[s][j] for j in range(want) for s in range(nset)]


def run_load(es, *, clients: int = 4, object_size: int = 1 << 20,
             put_frac: float = 0.5, duration_s: float = 5.0,
             bucket: str = "loadgen", warm_objects: int = 8,
             seed: int = 0, keyspace: str = "default",
             zipf: float | None = None,
             range_frac: float = 0.0,
             ilm_mix: float = 0.0, tier_mgr=None,
             tier_root: str | None = None,
             use_iter: bool = False,
             small: tuple[int, int] | None = None) -> dict:
    """Drive `clients` closed-loop workers against `es` for
    `duration_s`; returns aggregate GB/s, p50/p99 latency, and mean
    coalesced dispatch occupancy over the run.  `keyspace` picks the
    set-placement shape of every key touched (see keyspace_names);
    non-default modes add a per-set hit histogram and per-device lane
    dispatch stats to the result.

    `zipf` switches GET key choice from uniform to Zipf(s) over the
    warm set (rank 0 hottest) and adds hot-vs-cold p50/p99 SLO rows to
    the result; `range_frac` makes that fraction of GETs ranged
    (random aligned window), reported as their own SLO row.

    `use_iter` consumes GETs through get_object_iter — the serving
    path the HTTP handlers drive — measuring chunk lengths without
    materializing bytes, like a socket writer that hands each buffer
    to sendmsg.  This is the mode that exposes the zero-copy hot-view
    CPU saving; the default get_object path re-copies hot bodies in
    both flag modes.

    `ilm_mix` transitions that fraction of the warm set — its COLDEST
    Zipf ranks, the shape the scanner ages out — to a warm tier before
    the run; their GETs are served through stubs (head + tier
    read-through, the same path the HTTP handlers take) and tagged as
    their own stub_p50/p99 SLO row.  Pass a live `tier_mgr` to reuse
    one (ilm_bench does), else a DirTierBackend is stood up under
    `tier_root`.

    `small=(lo, hi)` switches to the small-object mix (ISSUE 19):
    every body size is drawn Zipf-skewed from a log-spaced ladder
    between `lo` and `hi` bytes (rank 0 = smallest, the real-world
    metadata-bound shape), `object_size` is ignored, and the result
    grows ops/s rows plus server-side `meta_*` deltas — amortized
    fsyncs/object, group-commit occupancy, and metadata read
    fan-outs/request — the group-commit plane's win metrics."""
    if not es.bucket_exists(bucket):
        es.make_bucket(bucket)
    rng = np.random.default_rng(seed)
    size_ladder: list[int] = []
    size_cdf = None
    warm_size: dict[str, int] = {}
    if small:
        lo, hi = small
        nsz = 12 if hi > lo else 1
        size_ladder = sorted({int(round(lo * (hi / lo) ** (i / max(1, nsz - 1))))
                              for i in range(nsz)})
        size_cdf = zipf_cdf(len(size_ladder), 1.1)
        bodies = {s: rng.integers(0, 256, s, dtype=np.uint8).tobytes()
                  for s in size_ladder}
        body = bodies[size_ladder[0]]
    else:
        body = rng.integers(0, 256, object_size,
                            dtype=np.uint8).tobytes()
    warm = keyspace_names(es, keyspace, total=max(1, warm_objects),
                          prefix="warm")
    for name in warm:
        if small:
            warm_size[name] = size_ladder[_zipf_pick(size_cdf, rng)]
            es.put_object(bucket, name, bodies[warm_size[name]])
        else:
            es.put_object(bucket, name, body)
    cdf = zipf_cdf(len(warm), zipf) if zipf else None
    cut = hot_rank_cut(len(warm))
    stub_names: set[str] = set()
    if ilm_mix > 0:
        from minio_tpu.bucket.tier import DirTierBackend, TierManager
        if tier_mgr is None:
            tier_mgr = TierManager(es)
        if not tier_mgr.list_tiers():
            root = tier_root or os.path.join(
                tempfile.mkdtemp(prefix="mtpu-loadgen-"), "tier")
            tier_mgr.add_tier("LGWARM", DirTierBackend(root))
        tname = tier_mgr.list_tiers()[0]
        ncold = max(1, min(len(warm), int(round(len(warm) * ilm_mix))))
        for name in warm[-ncold:]:       # coldest Zipf ranks age out
            if tier_mgr.transition_object(bucket, name, tname):
                stub_names.add(name)
    tier = getattr(es, "hot_tier", None) \
        or next((t for s in getattr(es, "sets", [])
                 if (t := getattr(s, "hot_tier", None)) is not None),
                None)
    tier0 = tier.stats() if tier is not None else None
    # PUT pool: placement-proven names partitioned per client (closed
    # loops overwrite within their own slice — no cross-client races).
    put_pool = keyspace_names(es, keyspace, total=max(clients * 8, 16),
                              prefix="put")
    put_slices = [put_pool[ci::clients] for ci in range(clients)]
    name_set: dict[str, int] = {}
    if keyspace != "default" and hasattr(es, "set_for"):
        name_set = {n: es.set_for(n).set_index
                    for n in warm + put_pool}

    stop = threading.Event()
    lat_put: list[list[float]] = [[] for _ in range(clients)]
    lat_get: list[list[float]] = [[] for _ in range(clients)]
    lat_hot: list[list[float]] = [[] for _ in range(clients)]
    lat_cold: list[list[float]] = [[] for _ in range(clients)]
    lat_ranged: list[list[float]] = [[] for _ in range(clients)]
    lat_stub: list[list[float]] = [[] for _ in range(clients)]
    nbytes = [0] * clients
    set_hits = [dict() for _ in range(clients)]
    errors: list[BaseException] = []

    def stub_get(name: str, off: int | None, ln: int | None) -> bytes:
        # The handlers' read path for transitioned versions: HEAD the
        # stub, stream the bytes back from the tier.  The engine's own
        # GET would return the stub's empty body (or raise out-of-range
        # for a ranged read against size 0).
        fi = es.head_object(bucket, name)
        if not tier_mgr.is_transitioned(fi) or fi.size > 0:
            # raced a restore: the hot copy is live again
            _, got = es.get_object(bucket, name, *(
                (off, ln) if off is not None else ()))
            return got
        if off is not None:
            return b"".join(tier_mgr.read_through_iter(fi, off, ln))
        return tier_mgr.read_through(fi)

    def client(ci: int) -> None:
        crng = np.random.default_rng(seed * 1000 + ci)
        mine = put_slices[ci]
        j = 0
        try:
            while not stop.is_set():
                is_put = crng.random() < put_frac
                t0 = time.monotonic()
                got_bytes = object_size
                rank = -1
                ranged = False
                is_stub = False
                if is_put:
                    name = (mine[j % len(mine)] if name_set
                            else f"c{ci}-{j}")
                    if small:
                        sz = size_ladder[_zipf_pick(size_cdf, crng)]
                        es.put_object(bucket, name, bodies[sz])
                        got_bytes = sz
                    else:
                        es.put_object(bucket, name, body)
                    j += 1
                else:
                    rank = (_zipf_pick(cdf, crng) if cdf is not None
                            else int(crng.integers(0, len(warm))))
                    name = warm[rank]
                    obj_sz = warm_size.get(name, object_size)
                    got_bytes = obj_sz
                    ranged = (range_frac > 0
                              and crng.random() < range_frac)
                    is_stub = name in stub_names
                    if ranged:
                        off = int(crng.integers(0, obj_sz))
                        ln = int(crng.integers(
                            1, obj_sz - off + 1))
                        if is_stub:
                            got_n = len(stub_get(name, off, ln))
                        elif use_iter:
                            _, it = es.get_object_iter(bucket, name,
                                                       off, ln)
                            got_n = sum(len(c) for c in it)
                        else:
                            _, got = es.get_object(bucket, name,
                                                   off, ln)
                            got_n = len(got)
                        got_bytes = ln
                        if got_n != ln:
                            raise AssertionError("short ranged read")
                    else:
                        if is_stub:
                            got_n = len(stub_get(name, None, None))
                        elif use_iter:
                            _, it = es.get_object_iter(bucket, name)
                            got_n = sum(len(c) for c in it)
                        else:
                            _, got = es.get_object(bucket, name)
                            got_n = len(got)
                        if got_n != obj_sz:
                            raise AssertionError("short read")
                dt = time.monotonic() - t0
                (lat_put if is_put else lat_get)[ci].append(dt)
                if not is_put:
                    if is_stub:
                        lat_stub[ci].append(dt)
                    elif ranged:
                        lat_ranged[ci].append(dt)
                    elif 0 <= rank < cut:
                        lat_hot[ci].append(dt)
                    else:
                        lat_cold[ci].append(dt)
                nbytes[ci] += got_bytes
                if name_set:
                    s = name_set.get(name, -1)
                    set_hits[ci][s] = set_hits[ci].get(s, 0) + 1
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)
            stop.set()

    snap0 = DATA_PATH.snapshot()
    # H2D boundary ledger + device-shard-cache deltas (ISSUE 17): how
    # many bytes crossed the host->device boundary per byte this run
    # moved, and how often verified shard batches were already
    # device-resident.  Import is lazy: the ledger lives next to the
    # cache and neither pulls in jax at import time.
    from minio_tpu.ops import devcache as _devcache
    h2d0 = _devcache.h2d_stats()
    dc0 = _devcache.stats()
    threads = [threading.Thread(target=client, args=(ci,), daemon=True)
               for ci in range(clients)]
    # CPU-seconds-per-GB attribution (ISSUE 16): the engine runs
    # in-process here, so RUSAGE_SELF over the run window IS the
    # server-side CPU bill for the bytes moved.
    import resource
    ru0 = resource.getrusage(resource.RUSAGE_SELF)
    t_start = time.monotonic()
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(60.0)
    wall = time.monotonic() - t_start
    ru1 = resource.getrusage(resource.RUSAGE_SELF)
    cpu_s = (ru1.ru_utime - ru0.ru_utime) + (ru1.ru_stime - ru0.ru_stime)
    snap1 = DATA_PATH.snapshot()
    h2d1 = _devcache.h2d_stats()
    dc1 = _devcache.stats()
    if errors:
        raise errors[0]

    puts = [x for per in lat_put for x in per]
    gets = [x for per in lat_get for x in per]
    alls = puts + gets
    d_disp = snap1["co_dispatches"] - snap0["co_dispatches"]
    d_items = snap1["co_items"] - snap0["co_items"]
    d_wait = snap1["co_wait_s"] - snap0["co_wait_s"]
    # digest lane deltas: how hard the PUT mix drove the native
    # multi-buffer MD5 plane (0s when MTPU_NATIVE_DIGEST=0)
    d_dg_calls = snap1["dg_md5_calls"] - snap0["dg_md5_calls"]
    d_dg_streams = snap1["dg_md5_streams"] - snap0["dg_md5_streams"]
    d_dg_bytes = snap1["dg_md5_bytes"] - snap0["dg_md5_bytes"]
    # per-device lane deltas (PR 10): which coalescer lanes dispatched,
    # how much, and at what batch occupancy over this run
    lanes0 = snap0.get("lanes", {})
    lane_dispatches: dict[int, int] = {}
    lane_occupancy: dict[int, float] = {}
    for dev, row in snap1.get("lanes", {}).items():
        prev = lanes0.get(dev, {})
        dd = row["dispatches"] - prev.get("dispatches", 0)
        di = row["items"] - prev.get("items", 0)
        if dd:
            lane_dispatches[dev] = dd
            lane_occupancy[dev] = round(di / dd, 3)
    merged_hits: dict[int, int] = {}
    for per in set_hits:
        for s, n in per.items():
            merged_hits[s] = merged_hits.get(s, 0) + n
    out = {
        "clients": clients,
        "object_size": object_size,
        "ops": len(alls),
        "puts": len(puts),
        "gets": len(gets),
        "wall_s": round(wall, 3),
        "gbps": round(sum(nbytes) / wall / 1e9, 3),
        # user+sys seconds burned per GB moved — the zero-copy
        # vertical's budget metric (lower = more kernel, less Python)
        "cpu_util": round(cpu_s / wall, 3) if wall else 0.0,
        "cpu_s_per_gb": round(cpu_s / (sum(nbytes) / 1e9), 3)
        if sum(nbytes) else 0.0,
        "p50_ms": round(_quantile(alls, 0.50) * 1e3, 3),
        "p99_ms": round(_quantile(alls, 0.99) * 1e3, 3),
        "put_p50_ms": round(_quantile(puts, 0.50) * 1e3, 3),
        "get_p50_ms": round(_quantile(gets, 0.50) * 1e3, 3),
        "co_dispatches": d_disp,
        "co_occupancy": round(d_items / d_disp, 3) if d_disp else 0.0,
        "co_wait_ms_per_item": round(d_wait / d_items * 1e3, 4)
        if d_items else 0.0,
        "dg_md5_calls": d_dg_calls,
        "dg_md5_occupancy": round(d_dg_streams / d_dg_calls, 3)
        if d_dg_calls else 0.0,
        "dg_md5_gbps": round(d_dg_bytes / wall / 1e9, 3),
        "keyspace": keyspace,
        "set_hits": {int(k): v for k, v in sorted(merged_hits.items())},
        "lane_dispatches": {int(k): v for k, v
                            in sorted(lane_dispatches.items())},
        "lane_occupancy": {int(k): v for k, v
                           in sorted(lane_occupancy.items())},
    }
    # Bytes-crossing-per-byte-served (ISSUE 17): ~1.0 on first touch,
    # ~0 when the device shard cache is absorbing the verify reads.
    total_b = sum(nbytes)
    d_h2d_b = h2d1["h2d_bytes"] - h2d0["h2d_bytes"]
    out["h2d_bytes"] = d_h2d_b
    out["h2d_dispatches"] = (h2d1["h2d_dispatches"]
                             - h2d0["h2d_dispatches"])
    out["h2d_bytes_per_byte"] = (round(d_h2d_b / total_b, 4)
                                 if total_b else 0.0)
    lane_h2d: dict[int, float] = {}
    for dev, row in h2d1["lanes"].items():
        db = (row["h2d_bytes"]
              - h2d0["lanes"].get(dev, {}).get("h2d_bytes", 0))
        if db:
            lane_h2d[int(dev)] = (round(db / total_b, 4)
                                  if total_b else 0.0)
    out["lane_h2d_bytes_per_byte"] = dict(sorted(lane_h2d.items()))
    if dc1 is not None:
        dh = dc1["hits"] - (dc0["hits"] if dc0 else 0)
        dm = dc1["misses"] - (dc0["misses"] if dc0 else 0)
        out["devcache_hits"] = dh
        out["devcache_misses"] = dm
        out["devcache_hit_ratio"] = (round(dh / (dh + dm), 4)
                                     if dh + dm else 0.0)
    if small:
        # Small-object rows (ISSUE 19): the mix is metadata-bound, so
        # ops/s (not GB/s) is the headline, and the server-side meta_*
        # deltas show what the group-commit plane amortized — fsyncs
        # per published object and journal batch occupancy.
        out["small_lo"] = small[0]
        out["small_hi"] = small[1]
        out["ops_per_s"] = round(len(alls) / wall, 1) if wall else 0.0
        out["put_ops_per_s"] = (round(len(puts) / wall, 1)
                                if wall else 0.0)
        out["get_ops_per_s"] = (round(len(gets) / wall, 1)
                                if wall else 0.0)
        d_pub = snap1["meta_publishes"] - snap0["meta_publishes"]
        d_fs = snap1["meta_fsyncs"] - snap0["meta_fsyncs"]
        d_gc = (snap1["meta_group_commits"]
                - snap0["meta_group_commits"])
        d_gi = snap1["meta_group_items"] - snap0["meta_group_items"]
        out["meta_fsyncs_per_object"] = (round(d_fs / d_pub, 4)
                                         if d_pub else 0.0)
        out["meta_batch_occupancy"] = (round(d_gi / d_gc, 3)
                                       if d_gc else 0.0)
    if zipf:
        out["zipf_s"] = zipf
        out.update(hot_cold_rows(
            [x for per in lat_hot for x in per],
            [x for per in lat_cold for x in per],
            [x for per in lat_ranged for x in per]))
    if ilm_mix > 0:
        stubs = [x for per in lat_stub for x in per]
        out["ilm_mix"] = ilm_mix
        out["stub_objects"] = len(stub_names)
        out["stub_gets"] = len(stubs)
        out["stub_p50_ms"] = round(_quantile(stubs, 0.50) * 1e3, 3)
        out["stub_p99_ms"] = round(_quantile(stubs, 0.99) * 1e3, 3)
        # exactly-once evidence: nothing left in flight after the run
        out["ilm_journal_pending"] = tier_mgr.journal.pending()
    if tier0 is not None:
        t1 = tier.stats()
        d_hits = t1["hits"] - tier0["hits"]
        d_miss = t1["misses"] - tier0["misses"]
        out["hotcache_hits"] = d_hits
        out["hotcache_misses"] = d_miss
        out["hotcache_hit_ratio"] = (
            round(d_hits / (d_hits + d_miss), 4)
            if d_hits + d_miss else 0.0)
        out["hotcache_fills"] = t1["fills"] - tier0["fills"]
    return out


def _http_clients_loop(endpoint: str, creds: tuple[str, str],
                       bucket: str, warm: list[str], body: bytes,
                       clients: int, put_frac: float,
                       duration_s: float, seed: int,
                       tag_pools: bool = False,
                       zipf: float | None = None,
                       range_frac: float = 0.0,
                       stub_names: frozenset = frozenset()) -> dict:
    """One load PROCESS: `clients` closed-loop threads, each with its
    own S3Client (own connections).  Returns picklable lat/byte tallies
    so --procs can merge across forks.  tag_pools reads the
    x-mtpu-pool response header off every PUT (multi-pool placement
    histogram — --during-decom's skew evidence); zipf/range_frac mirror
    run_load's Zipfian GET mix.  GETs of `stub_names` (warm keys the
    caller transitioned to a tier) are issued raw so the x-amz-
    storage-class response header can be checked — proof the bytes
    came through a stub — and tagged as their own lat_stub bucket."""
    from minio_tpu.server.client import S3Client
    stop = threading.Event()
    lat_put: list[list[float]] = [[] for _ in range(clients)]
    lat_get: list[list[float]] = [[] for _ in range(clients)]
    lat_hot: list[list[float]] = [[] for _ in range(clients)]
    lat_cold: list[list[float]] = [[] for _ in range(clients)]
    lat_ranged: list[list[float]] = [[] for _ in range(clients)]
    lat_stub: list[list[float]] = [[] for _ in range(clients)]
    nbytes = [0] * clients
    pool_hits: list[dict[str, int]] = [dict() for _ in range(clients)]
    stub_noclass = [0] * clients
    errors: list[str] = []
    cdf = zipf_cdf(len(warm), zipf) if zipf else None
    cut = hot_rank_cut(len(warm))

    def client(ci: int) -> None:
        cli = S3Client(endpoint, creds[0], creds[1])
        crng = np.random.default_rng(seed * 1000 + ci)
        j = 0
        try:
            while not stop.is_set():
                is_put = crng.random() < put_frac
                t0 = time.monotonic()
                got_bytes = len(body)
                rank = -1
                ranged = False
                if is_put:
                    h = cli.put_object(bucket, f"p{seed}-c{ci}-{j}",
                                       body)
                    j += 1
                    if tag_pools:
                        p = (h.get("x-mtpu-pool")
                             or h.get("X-Mtpu-Pool") or "?")
                        pool_hits[ci][p] = pool_hits[ci].get(p, 0) + 1
                else:
                    rank = (_zipf_pick(cdf, crng) if cdf is not None
                            else int(crng.integers(0, len(warm))))
                    name = warm[rank]
                    ranged = (range_frac > 0
                              and crng.random() < range_frac)
                    is_stub = name in stub_names
                    if ranged:
                        off = int(crng.integers(0, len(body)))
                        end = int(crng.integers(off, len(body)))
                        got_bytes = end - off + 1
                        if is_stub:
                            st, h, got = cli.request(
                                "GET", f"/{bucket}/{name}",
                                headers={"Range":
                                         f"bytes={off}-{end}"})
                            if st != 206:
                                raise AssertionError(
                                    f"stub ranged GET -> {st}")
                            if not (h.get("x-amz-storage-class") or
                                    h.get("X-Amz-Storage-Class")):
                                stub_noclass[ci] += 1
                        else:
                            got = cli.get_object(bucket, name,
                                                 range_=(off, end))
                        if len(got) != got_bytes:
                            raise AssertionError("short ranged read")
                    else:
                        if is_stub:
                            st, h, got = cli.request(
                                "GET", f"/{bucket}/{name}")
                            if st != 200:
                                raise AssertionError(
                                    f"stub GET -> {st}")
                            if not (h.get("x-amz-storage-class") or
                                    h.get("X-Amz-Storage-Class")):
                                stub_noclass[ci] += 1
                        else:
                            got = cli.get_object(bucket, name)
                        if len(got) != len(body):
                            raise AssertionError("short read")
                dt = time.monotonic() - t0
                (lat_put if is_put else lat_get)[ci].append(dt)
                if not is_put:
                    if is_stub:
                        lat_stub[ci].append(dt)
                    elif ranged:
                        lat_ranged[ci].append(dt)
                    elif 0 <= rank < cut:
                        lat_hot[ci].append(dt)
                    else:
                        lat_cold[ci].append(dt)
                nbytes[ci] += got_bytes
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(f"{type(e).__name__}: {e}")
            stop.set()

    threads = [threading.Thread(target=client, args=(ci,), daemon=True)
               for ci in range(clients)]
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(60.0)
    merged: dict[str, int] = {}
    for per in pool_hits:
        for p, n in per.items():
            merged[p] = merged.get(p, 0) + n
    return {"lat_put": [x for per in lat_put for x in per],
            "lat_get": [x for per in lat_get for x in per],
            "lat_hot": [x for per in lat_hot for x in per],
            "lat_cold": [x for per in lat_cold for x in per],
            "lat_ranged": [x for per in lat_ranged for x in per],
            "lat_stub": [x for per in lat_stub for x in per],
            "stub_noclass": sum(stub_noclass),
            "nbytes": sum(nbytes), "errors": errors,
            "pool_hits": merged}


def run_load_http(endpoint: str, *, clients: int = 4,
                  object_size: int = 1 << 20, put_frac: float = 0.5,
                  duration_s: float = 5.0, bucket: str = "loadgen",
                  warm_objects: int = 8, seed: int = 0, procs: int = 1,
                  access_key: str = "minioadmin",
                  secret_key: str = "minioadmin",
                  tag_pools: bool = False,
                  zipf: float | None = None,
                  range_frac: float = 0.0,
                  ilm_mix: float = 0.0,
                  tier_path: str | None = None,
                  server_pid: int | None = None) -> dict:
    """HTTP closed loop against a running endpoint; with procs>1 the
    `clients` are spread over that many forked client processes.

    `server_pid` (a LOCAL server process) adds server_cpu_util and
    server_cpu_s_per_gb columns from /proc/<pid>/stat across the
    process tree (MTPU_WORKERS children included) — the server-side
    CPU bill per byte served, the zero-copy budget metric.  Without
    it only client_cpu_util is reported, and that is CLIENT-side CPU
    (SigV4 signing + socket reads), not the server's.
    tag_pools adds a pool_hits histogram (PUTs per placement pool,
    from the x-mtpu-pool response header) — run it against a server
    mid-decommission and the draining pool must show zero hits.

    `ilm_mix` registers an fs warm tier through the admin plane (at
    `tier_path`, which must be a directory the SERVER can reach — this
    mode assumes a local endpoint) and transitions that fraction of
    the warm set's coldest ranks before the run; their GETs come back
    through stubs and are reported as stub_p50/p99 rows, with the
    x-amz-storage-class response header checked on every one."""
    import json as _json
    import multiprocessing as mp
    from minio_tpu.server.client import S3Client

    cli = S3Client(endpoint, access_key, secret_key)
    if not cli.bucket_exists(bucket):
        cli.make_bucket(bucket)
    rng = np.random.default_rng(seed)
    body = rng.integers(0, 256, object_size, dtype=np.uint8).tobytes()
    warm = [f"warm-{i}" for i in range(max(1, warm_objects))]
    for name in warm:
        cli.put_object(bucket, name, body)

    stub_names: frozenset = frozenset()
    if ilm_mix > 0:
        tname = "LGWARM"
        path = tier_path or tempfile.mkdtemp(prefix="mtpu-lg-tier-")
        st, _, rb = cli.request(
            "POST", "/minio/admin/v3/tier",
            body=_json.dumps({"name": tname, "type": "fs",
                              "path": path}).encode(),
            headers={"Content-Type": "application/json"})
        # 409 = tier already registered from an earlier run: reuse it
        if st not in (200, 409):
            raise RuntimeError(f"tier add -> {st}: {rb[:200]!r}")
        moved = []
        ncold = max(1, min(len(warm),
                           int(round(len(warm) * ilm_mix))))
        for name in warm[-ncold:]:       # coldest Zipf ranks age out
            st, _, rb = cli.request(
                "POST", "/minio/admin/v3/ilm",
                body=_json.dumps({"bucket": bucket, "object": name,
                                  "tier": tname}).encode(),
                headers={"Content-Type": "application/json"})
            if st != 200:
                raise RuntimeError(
                    f"transition {name} -> {st}: {rb[:200]!r}")
            if _json.loads(rb).get("transitioned"):
                moved.append(name)
        stub_names = frozenset(moved)

    procs = max(1, min(procs, clients))
    # spread clients over processes; earlier procs take the remainder
    per = [clients // procs + (1 if i < clients % procs else 0)
           for i in range(procs)]
    creds = (access_key, secret_key)
    import resource
    ru0 = resource.getrusage(resource.RUSAGE_SELF)
    srv_cpu0 = _proc_tree_cpu_s(server_pid) if server_pid else None
    t_start = time.monotonic()
    if procs == 1:
        parts = [_http_clients_loop(endpoint, creds, bucket, warm, body,
                                    clients, put_frac, duration_s,
                                    seed, tag_pools, zipf, range_frac,
                                    stub_names)]
    else:
        ctx = mp.get_context("fork")
        q: mp.Queue = ctx.Queue()

        def entry(i: int, n: int) -> None:
            q.put(_http_clients_loop(endpoint, creds, bucket, warm,
                                     body, n, put_frac, duration_s,
                                     seed + i, tag_pools, zipf,
                                     range_frac, stub_names))

        ps = [ctx.Process(target=entry, args=(i, n), daemon=True)
              for i, n in enumerate(per) if n]
        for p in ps:
            p.start()
        parts = [q.get(timeout=duration_s + 120) for _ in ps]
        for p in ps:
            p.join(30.0)
    wall = time.monotonic() - t_start
    ru1 = resource.getrusage(resource.RUSAGE_SELF)
    srv_cpu1 = _proc_tree_cpu_s(server_pid) if server_pid else None
    errs = [e for part in parts for e in part["errors"]]
    if errs:
        raise RuntimeError(f"loadgen client error: {errs[0]}")
    puts = [x for part in parts for x in part["lat_put"]]
    gets = [x for part in parts for x in part["lat_get"]]
    alls = puts + gets
    total_bytes = sum(p["nbytes"] for p in parts)
    res = {
        "endpoint": endpoint, "clients": clients, "procs": procs,
        "object_size": object_size,
        "ops": len(alls), "puts": len(puts), "gets": len(gets),
        "wall_s": round(wall, 3),
        "gbps": round(total_bytes / wall / 1e9, 3),
        # CLIENT-side CPU (signing, socket reads) — NOT the server's;
        # forked --procs workers bill their own rusage, so this row is
        # only the coordinating process and is indicative at best.
        "client_cpu_util": round(
            ((ru1.ru_utime - ru0.ru_utime)
             + (ru1.ru_stime - ru0.ru_stime)) / wall, 3)
        if wall else 0.0,
        "p50_ms": round(_quantile(alls, 0.50) * 1e3, 3),
        "p99_ms": round(_quantile(alls, 0.99) * 1e3, 3),
        "put_p50_ms": round(_quantile(puts, 0.50) * 1e3, 3),
        "get_p50_ms": round(_quantile(gets, 0.50) * 1e3, 3),
    }
    if srv_cpu0 is not None and srv_cpu1 is not None:
        srv_cpu = max(0.0, srv_cpu1 - srv_cpu0)
        res["server_cpu_util"] = round(srv_cpu / wall, 3) if wall else 0.0
        res["server_cpu_s_per_gb"] = round(
            srv_cpu / (total_bytes / 1e9), 3) if total_bytes else 0.0
    if zipf:
        res["zipf_s"] = zipf
        res.update(hot_cold_rows(
            [x for p in parts for x in p.get("lat_hot", [])],
            [x for p in parts for x in p.get("lat_cold", [])],
            [x for p in parts for x in p.get("lat_ranged", [])]))
    if ilm_mix > 0:
        stubs = [x for p in parts for x in p.get("lat_stub", [])]
        noclass = sum(p.get("stub_noclass", 0) for p in parts)
        res["ilm_mix"] = ilm_mix
        res["stub_objects"] = len(stub_names)
        res["stub_gets"] = len(stubs)
        res["stub_p50_ms"] = round(_quantile(stubs, 0.50) * 1e3, 3)
        res["stub_p99_ms"] = round(_quantile(stubs, 0.99) * 1e3, 3)
        # every stub GET must carry the tier's storage class — 0 here
        # means every tagged read provably came through a stub
        res["stub_missing_storage_class"] = noclass
    if tag_pools:
        merged: dict[str, int] = {}
        for part in parts:
            for p, n in part.get("pool_hits", {}).items():
                merged[p] = merged.get(p, 0) + n
        res["pool_hits"] = dict(sorted(merged.items()))
    return res


def parse_tenant_spec(spec: str) -> list[dict]:
    """Parse --tenants 'name:class:clients[:rps],...' into tenant rows.
    `name` doubles as the tenant's access key (the identity the QoS
    plane's MTPU_QOS_TENANTS map classes by); `class` is one of
    premium/standard/best-effort; `clients` is the tenant's closed-loop
    concurrency; optional `rps` caps the tenant's offered request rate
    client-side (0 = closed-loop, as fast as the server admits)."""
    out = []
    for frag in spec.split(","):
        frag = frag.strip()
        if not frag:
            continue
        parts = frag.split(":")
        if len(parts) not in (3, 4):
            raise ValueError(
                f"tenant spec {frag!r}: want name:class:clients[:rps]")
        name, klass, clients = parts[0], parts[1], int(parts[2])
        if klass not in ("premium", "standard", "best-effort"):
            raise ValueError(f"tenant spec {frag!r}: unknown class "
                             f"{klass!r}")
        if clients < 1:
            raise ValueError(f"tenant spec {frag!r}: clients < 1")
        rps = float(parts[3]) if len(parts) == 4 else 0.0
        out.append({"name": name, "class": klass, "clients": clients,
                    "rps": rps})
    if not out:
        raise ValueError("empty tenant spec")
    return out


def _tenant_loop(endpoint: str, creds: tuple[str, str], bucket: str,
                 warm: list[str], body: bytes, clients: int,
                 put_frac: float, duration_s: float, seed: int,
                 rps: float) -> dict:
    """One tenant's client group: closed-loop threads signing with the
    TENANT's credentials, issuing raw requests so shed responses (503
    SlowDown) are COUNTED rather than raised — under deliberate
    overload, sheds are data, not failures.  Returns goodput (bytes of
    ops that succeeded), per-op latencies of successful ops only, and
    the shed/error tallies the QoS acceptance gates compare."""
    from minio_tpu.server.client import S3Client
    stop = threading.Event()
    lat_ok: list[list[float]] = [[] for _ in range(clients)]
    ok = [0] * clients
    shed = [0] * clients
    errs = [0] * clients
    nbytes = [0] * clients
    fatal: list[str] = []
    # client-side pacing: rps is the TENANT's offered rate, spread
    # evenly over its threads (0 = pure closed loop)
    per_thread_interval = clients / rps if rps > 0 else 0.0

    def client(ci: int) -> None:
        cli = S3Client(endpoint, creds[0], creds[1])
        crng = np.random.default_rng(seed * 1000 + ci)
        j = 0
        next_t = time.monotonic()
        try:
            while not stop.is_set():
                if per_thread_interval:
                    now = time.monotonic()
                    if now < next_t:
                        time.sleep(min(next_t - now, 0.25))
                        continue
                    next_t += per_thread_interval
                is_put = crng.random() < put_frac
                t0 = time.monotonic()
                try:
                    if is_put:
                        name = f"{creds[0]}-c{ci}-{j % 64}"
                        j += 1
                        st, _, rb = cli.request(
                            "PUT", f"/{bucket}/{name}", body=body)
                        moved = len(body)
                    else:
                        rank = int(crng.integers(0, len(warm)))
                        st, _, rb = cli.request(
                            "GET", f"/{bucket}/{warm[rank]}")
                        moved = len(rb)
                except (ConnectionError, TimeoutError, OSError):
                    # Shed responses close the connection; a pooled
                    # client racing that close sees a reset.  Under
                    # deliberate overload that's shed fallout, not a
                    # server error — reconnect and count it as shed.
                    cli = S3Client(endpoint, creds[0], creds[1])
                    shed[ci] += 1
                    continue
                dt = time.monotonic() - t0
                if st in (200, 206):
                    ok[ci] += 1
                    nbytes[ci] += moved
                    lat_ok[ci].append(dt)
                elif st == 503 and b"SlowDown" in rb:
                    shed[ci] += 1          # admission/throttle shed
                else:
                    errs[ci] += 1
        except BaseException as e:  # noqa: BLE001 — surfaced below
            fatal.append(f"{type(e).__name__}: {e}")
            stop.set()

    threads = [threading.Thread(target=client, args=(ci,), daemon=True)
               for ci in range(clients)]
    t_start = time.monotonic()
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(60.0)
    wall = time.monotonic() - t_start
    if fatal:
        raise RuntimeError(f"tenant {creds[0]} client error: {fatal[0]}")
    lats = [x for per in lat_ok for x in per]
    n_ok, n_shed, n_err = sum(ok), sum(shed), sum(errs)
    total = n_ok + n_shed + n_err
    return {
        "ok": n_ok, "shed": n_shed, "errors": n_err,
        "attempts": total,
        "shed_rate": round(n_shed / total, 4) if total else 0.0,
        "goodput_gbps": round(sum(nbytes) / wall / 1e9, 4),
        "goodput_rps": round(n_ok / wall, 1),
        "p50_ms": round(_quantile(lats, 0.50) * 1e3, 3),
        "p99_ms": round(_quantile(lats, 0.99) * 1e3, 3),
    }


def run_load_tenants(endpoint: str, *, tenants: list[dict],
                     object_size: int = 1 << 20, put_frac: float = 0.5,
                     duration_s: float = 5.0, bucket: str = "loadgen",
                     warm_objects: int = 8, seed: int = 0,
                     access_key: str = "minioadmin",
                     secret_key: str = "minioadmin") -> dict:
    """Multi-tenant HTTP load: provision one IAM user per tenant (the
    access key the server's MTPU_QOS_TENANTS map classes), then run
    every tenant's client group CONCURRENTLY against the same bucket
    and report per-tenant goodput + p50/p99 + shed rows — the table
    where per-class isolation under overload either shows up or
    doesn't.  Root credentials (`access_key`/`secret_key`) provision
    users and warm the keyspace; tenants sign with their own."""
    import json as _json
    from minio_tpu.server.client import S3Client

    cli = S3Client(endpoint, access_key, secret_key)
    if not cli.bucket_exists(bucket):
        cli.make_bucket(bucket)
    for t in tenants:
        st, _, rb = cli.request(
            "POST", "/minio/admin/v3/users",
            body=_json.dumps({"accessKey": t["name"],
                              "secretKey": tenant_secret(t["name"]),
                              "policies": ["readwrite"]}).encode(),
            headers={"Content-Type": "application/json"})
        if st != 200:
            raise RuntimeError(
                f"user add {t['name']} -> {st}: {rb[:200]!r}")
    rng = np.random.default_rng(seed)
    body = rng.integers(0, 256, object_size, dtype=np.uint8).tobytes()
    warm = [f"warm-{i}" for i in range(max(1, warm_objects))]
    for name in warm:
        cli.put_object(bucket, name, body)

    results: dict[str, dict] = {}
    errors: list[BaseException] = []

    def run_one(i: int, t: dict) -> None:
        try:
            results[t["name"]] = _tenant_loop(
                endpoint, (t["name"], tenant_secret(t["name"])),
                bucket, warm, body, t["clients"], put_frac,
                duration_s, seed + 7919 * (i + 1), t["rps"])
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    runners = [threading.Thread(target=run_one, args=(i, t),
                                daemon=True)
               for i, t in enumerate(tenants)]
    t_start = time.monotonic()
    for r in runners:
        r.start()
    for r in runners:
        r.join(duration_s + 120)
    wall = time.monotonic() - t_start
    if errors:
        raise errors[0]
    rows = {}
    for t in tenants:
        row = dict(results[t["name"]])
        row["class"] = t["class"]
        row["clients"] = t["clients"]
        if t["rps"]:
            row["offered_rps"] = t["rps"]
        rows[t["name"]] = row
    return {
        "endpoint": endpoint, "object_size": object_size,
        "duration_s": duration_s, "wall_s": round(wall, 3),
        "total_goodput_gbps": round(
            sum(r["goodput_gbps"] for r in rows.values()), 4),
        "total_ok": sum(r["ok"] for r in rows.values()),
        "total_shed": sum(r["shed"] for r in rows.values()),
        "total_errors": sum(r["errors"] for r in rows.values()),
        "tenants": rows,
    }


def tenant_secret(name: str) -> str:
    """Deterministic per-tenant secret key: tests and bench legs
    re-derive it instead of plumbing credentials around."""
    return f"{name}-tenant-secret"


def print_tenant_report(res: dict) -> None:
    """Human table for run_load_tenants output: one SLO row per
    tenant — the isolation evidence at a glance."""
    print(f"total goodput {res['total_goodput_gbps']} GB/s, "
          f"ok {res['total_ok']}, shed {res['total_shed']}, "
          f"errors {res['total_errors']}")
    print(f"{'tenant':<16}{'class':<14}{'clients':>8}{'ok':>8}"
          f"{'shed':>8}{'err':>6}{'shed%':>8}{'GB/s':>8}"
          f"{'p50_ms':>9}{'p99_ms':>9}")
    for name, r in res["tenants"].items():
        print(f"{name:<16}{r['class']:<14}{r['clients']:>8}"
              f"{r['ok']:>8}{r['shed']:>8}{r['errors']:>6}"
              f"{100 * r['shed_rate']:>7.1f}%{r['goodput_gbps']:>8}"
              f"{r['p50_ms']:>9}{r['p99_ms']:>9}")


def slo_report(endpoint: str, access_key: str, secret_key: str) -> dict:
    """Scrape the server's last-minute SLO window after a run: the
    mtpu_api_last_minute_{count,errors,p50,p99} families from
    /minio/v2/metrics/node, keyed by API.  Client-side latencies above
    measure the wire; this is the server's own view of the same window
    — the two disagreeing is itself a finding (queueing outside the
    handler).  Empty when the server runs with MTPU_SLO=0."""
    import re
    from minio_tpu.server.client import S3Client

    cli = S3Client(endpoint, access_key, secret_key)
    st, _, body = cli.request("GET", "/minio/v2/metrics/node")
    if st != 200:
        return {}
    out: dict[str, dict[str, float]] = {}
    pat = re.compile(r'^mtpu_api_last_minute_(\w+)\{api="([^"]+)"\} '
                     r'([0-9.eE+-]+)$')
    for line in body.decode().splitlines():
        m = pat.match(line)
        if m:
            out.setdefault(m.group(2), {})[m.group(1)] = \
                float(m.group(3))
    return out


def repl_report(endpoint: str, access_key: str, secret_key: str) -> dict:
    """Scrape the replication plane's counters after a run: the
    mtpu_repl_* families from /minio/v2/metrics/node.  One SLO row —
    a run that left a backlog (journal_pending > 0) or positive lag is
    reporting durable-but-not-yet-mirrored writes, not loss.  Empty
    when the server has no replication pool wired."""
    import re
    from minio_tpu.server.client import S3Client

    cli = S3Client(endpoint, access_key, secret_key)
    st, _, body = cli.request("GET", "/minio/v2/metrics/node")
    if st != 200:
        return {}
    out: dict[str, float] = {}
    pat = re.compile(r'^mtpu_repl_(\w+)(?:\{[^}]*\})? ([0-9.eE+-]+)$')
    for line in body.decode().splitlines():
        m = pat.match(line)
        if m:
            name, val = m.group(1), float(m.group(2))
            # lag is per-target labelled; keep the worst target
            out[name] = max(out.get(name, 0.0), val)
    return out


def make_set(root: str, n: int = 4, parity: int | None = None):
    from minio_tpu.engine.erasure_set import ErasureSet
    drives = [LocalDrive(os.path.join(root, f"d{i}")) for i in range(n)]
    return ErasureSet(drives, default_parity=parity)


def make_sets(root: str, nsets: int = 4, set_drives: int = 4,
              parity: int | None = None):
    """A full hash ring (nsets erasure sets of set_drives drives) —
    the topology the --keyspace modes route across."""
    from minio_tpu.engine.sets import ErasureSets
    drives = [LocalDrive(os.path.join(root, f"d{i}"))
              for i in range(nsets * set_drives)]
    return ErasureSets(drives, set_drive_count=set_drives,
                       default_parity=parity)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--size-kib", type=int, default=1024)
    ap.add_argument("--mix", type=float, default=0.5,
                    help="PUT fraction (rest are GETs)")
    ap.add_argument("--duration", type=float, default=5.0)
    ap.add_argument("--drives", type=int, default=4)
    ap.add_argument("--parity", type=int, default=None)
    ap.add_argument("--sets", type=int, default=1,
                    help="engine mode: build a hash ring of N erasure "
                    "sets (of --drives each) instead of one bare set — "
                    "the topology --keyspace routes across")
    ap.add_argument("--keyspace", choices=("default", "spread",
                                           "pinned"),
                    default="default",
                    help="spread: keys provably fan out over every "
                    "erasure set (all device lanes busy); pinned: all "
                    "keys land on set 0 (one lane hot).  The output's "
                    "set_hits histogram proves the placement")
    ap.add_argument("--zipf", type=float, nargs="?", const=1.1,
                    default=None, metavar="S",
                    help="Zipf(s) GET key skew over the warm set "
                    "(rank 0 hottest; bare --zipf means s=1.1). "
                    "Adds hot-key vs cold-key p50/p99 SLO rows — the "
                    "split the hot-object cache must win")
    ap.add_argument("--small", nargs="?", const="4,64",
                    default=None, metavar="N[,M]",
                    help="small-object mix (engine mode): body sizes "
                    "drawn Zipf-skewed from a log ladder between N and "
                    "M KiB (bare --small means 4,64 — the inline "
                    "small-object band).  Reports ops/s, p50/p99, and "
                    "server-side meta_* deltas: amortized "
                    "fsyncs/object, group-commit occupancy, and "
                    "metadata read fan-outs/request")
    ap.add_argument("--range-frac", type=float, default=0.0,
                    help="fraction of GETs issued as random ranged "
                    "reads (their own SLO row)")
    ap.add_argument("--ilm-mix", type=float, default=0.0,
                    metavar="FRAC",
                    help="transition FRAC of the warm set's coldest "
                    "ranks to a warm tier before the run and tag "
                    "their GETs — served through ILM stubs — as their "
                    "own stub_p50/p99 SLO row.  Engine mode reads "
                    "through a local dir tier; HTTP mode registers an "
                    "fs tier via the admin plane (local endpoint) and "
                    "checks x-amz-storage-class on every stub GET")
    ap.add_argument("--warm-objects", type=int, default=None,
                    help="warm GET keyspace size (default 8, or 64 "
                    "under --zipf so the skew has a tail)")
    ap.add_argument("--root", default="/tmp/mtpu-loadgen")
    ap.add_argument("--endpoint", default="",
                    help="http(s)://host:port — drive a RUNNING server "
                    "over the wire instead of an in-process engine")
    ap.add_argument("--procs", type=int, default=1,
                    help="HTTP mode: fork the client side into N "
                    "processes (clients are spread across them)")
    ap.add_argument("--access-key",
                    default=os.environ.get("MTPU_ROOT_USER",
                                           "minioadmin"))
    ap.add_argument("--secret-key",
                    default=os.environ.get("MTPU_ROOT_PASSWORD",
                                           "minioadmin"))
    ap.add_argument("--profile", choices=("mixed", "put-digest"),
                    default="mixed",
                    help="put-digest: PUT-only 4 MiB objects — the "
                    "ETag-digest-bound shape the multi-buffer MD5 "
                    "lanes exist for (dg_md5_* in the output show "
                    "lane occupancy and aggregate hash rate)")
    ap.add_argument("--server-pid", type=int, default=None,
                    help="HTTP mode: pid of the LOCAL server — adds "
                    "server_cpu_util / server_cpu_s_per_gb columns "
                    "from /proc across its worker tree (the zero-copy "
                    "CPU-per-GB budget).  Engine mode reports this "
                    "inherently via cpu_util/cpu_s_per_gb: the engine "
                    "runs in-process, so rusage IS the server bill")
    ap.add_argument("--tenants", default="", metavar="SPEC",
                    help="HTTP mode: multi-tenant run — comma list of "
                    "name:class:clients[:rps] (class one of premium/"
                    "standard/best-effort; name doubles as the IAM "
                    "access key the server's MTPU_QOS_TENANTS map "
                    "classes).  Provisions the users, runs every "
                    "tenant's client group concurrently, and reports "
                    "per-tenant goodput + p50/p99 + shed rows")
    ap.add_argument("--during-decom", action="store_true",
                    help="HTTP mode: tag every PUT with the pool it "
                    "landed on (x-mtpu-pool response header) and "
                    "report a pool_hits placement-skew histogram — "
                    "run it against a server mid-decommission to "
                    "prove new writes avoid the draining pool")
    args = ap.parse_args(argv)
    small = None
    if args.small is not None:
        parts = [p for p in str(args.small).split(",") if p]
        try:
            lo = int(parts[0])
            hi = int(parts[1]) if len(parts) > 1 else 64
        except (ValueError, IndexError):
            print(f"--small expects N or N,M in KiB, got "
                  f"{args.small!r}", file=sys.stderr)
            return 2
        if lo <= 0 or hi < lo:
            print(f"--small bounds must satisfy 0 < N <= M, got "
                  f"{args.small!r}", file=sys.stderr)
            return 2
        small = (lo << 10, hi << 10)
        if args.endpoint:
            print("--small is engine-mode only (the meta_* deltas "
                  "come from the in-process DATA_PATH ledger)",
                  file=sys.stderr)
            return 2
        if args.zipf is None:      # sizes ride the Zipf key picker
            args.zipf = 1.1
    if args.during_decom and not args.endpoint:
        print("--during-decom requires --endpoint (the x-mtpu-pool "
              "header is an HTTP response surface)", file=sys.stderr)
        return 2
    if args.profile == "put-digest":
        args.mix = 1.0
        if args.size_kib == 1024:          # only override the default
            args.size_kib = 4096

    warm_objects = (args.warm_objects if args.warm_objects is not None
                    else (64 if args.zipf else 8))
    if args.tenants:
        if not args.endpoint:
            print("--tenants requires --endpoint (tenants are IAM "
                  "identities on a running server)", file=sys.stderr)
            return 2
        res = run_load_tenants(args.endpoint,
                               tenants=parse_tenant_spec(args.tenants),
                               object_size=args.size_kib << 10,
                               put_frac=args.mix,
                               duration_s=args.duration,
                               warm_objects=warm_objects,
                               access_key=args.access_key,
                               secret_key=args.secret_key)
        print_tenant_report(res)
        return 0
    if args.endpoint:
        res = run_load_http(args.endpoint, clients=args.clients,
                            object_size=args.size_kib << 10,
                            put_frac=args.mix,
                            duration_s=args.duration,
                            warm_objects=warm_objects,
                            procs=args.procs,
                            access_key=args.access_key,
                            secret_key=args.secret_key,
                            tag_pools=args.during_decom,
                            zipf=args.zipf,
                            range_frac=args.range_frac,
                            ilm_mix=args.ilm_mix,
                            server_pid=args.server_pid)
    else:
        es = (make_sets(args.root, nsets=args.sets,
                        set_drives=args.drives, parity=args.parity)
              if args.sets > 1
              else make_set(args.root, n=args.drives,
                            parity=args.parity))
        from minio_tpu.engine.hotcache import attach_sets, maybe_tier
        tier = maybe_tier()
        if tier is not None:
            attach_sets(es, tier)
        res = run_load(es, clients=args.clients,
                       object_size=args.size_kib << 10,
                       put_frac=args.mix, duration_s=args.duration,
                       warm_objects=warm_objects,
                       keyspace=args.keyspace, zipf=args.zipf,
                       range_frac=args.range_frac,
                       ilm_mix=args.ilm_mix,
                       tier_root=os.path.join(args.root, "tier"),
                       small=small)
    w = max(len(k) for k in res)
    for k, v in res.items():
        print(f"{k:<{w}}  {v}")
    if args.endpoint:
        try:
            slo = slo_report(args.endpoint, args.access_key,
                             args.secret_key)
        except Exception as e:  # noqa: BLE001 — report is best-effort
            print(f"\n(slo report unavailable: {e})", file=sys.stderr)
            slo = {}
        if slo:
            print("\nserver last-minute SLO window "
                  "(mtpu_api_last_minute_*):")
            print(f"{'api':<24}{'count':>8}{'errors':>8}"
                  f"{'p50_ms':>10}{'p99_ms':>10}")
            for api, d in sorted(slo.items()):
                print(f"{api:<24}{int(d.get('count', 0)):>8}"
                      f"{int(d.get('errors', 0)):>8}"
                      f"{d.get('p50', 0.0):>10.1f}"
                      f"{d.get('p99', 0.0):>10.1f}")
        try:
            repl = repl_report(args.endpoint, args.access_key,
                               args.secret_key)
        except Exception as e:  # noqa: BLE001 — report is best-effort
            print(f"\n(repl report unavailable: {e})", file=sys.stderr)
            repl = {}
        if repl:
            print("\nreplication plane (mtpu_repl_*): "
                  f"completed={int(repl.get('completed_total', 0))} "
                  f"failed={int(repl.get('failed_total', 0))} "
                  f"retries={int(repl.get('retries_total', 0))} "
                  f"backlog={int(repl.get('journal_pending', 0))} "
                  f"worst_lag_s={repl.get('lag_seconds', 0.0):.2f} "
                  f"MiB={repl.get('bytes_total', 0.0) / 2**20:.1f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
