"""Prometheus metrics: counters/gauges/histograms + text exposition.

The cmd/metrics-v2.go equivalent: API request/error counters by handler,
in-flight gauge, latency histogram, plus cluster families (capacity,
object/bucket counts from the scanner usage tree, heal stats). Rendered
in the Prometheus text format at /minio/v2/metrics/{cluster,node}.
"""

from __future__ import annotations

import threading

from .lastminute import ApiWindow


class Counter:
    def __init__(self, name: str, help_: str, label_names=()):
        self.name = name
        self.help = help_
        self.label_names = tuple(label_names)
        self._mu = threading.Lock()
        self._values: dict[tuple, float] = {}

    def inc(self, amount: float = 1.0, **labels) -> None:
        key = tuple(labels.get(n, "") for n in self.label_names)
        with self._mu:
            self._values[key] = self._values.get(key, 0.0) + amount

    def get(self, **labels) -> float:
        key = tuple(labels.get(n, "") for n in self.label_names)
        with self._mu:
            return self._values.get(key, 0.0)

    def render(self, out: list) -> None:
        out.append(f"# HELP {self.name} {self.help}")
        out.append(f"# TYPE {self.name} counter")
        with self._mu:
            if not self._values:
                out.append(f"{self.name} 0")
            for key, v in sorted(self._values.items()):
                lbl = ",".join(f'{n}="{val}"' for n, val in
                               zip(self.label_names, key))
                out.append(f"{self.name}{{{lbl}}} {v:g}" if lbl
                           else f"{self.name} {v:g}")


class Gauge(Counter):
    def set(self, value: float, **labels) -> None:
        key = tuple(labels.get(n, "") for n in self.label_names)
        with self._mu:
            self._values[key] = value

    def render(self, out: list) -> None:
        out.append(f"# HELP {self.name} {self.help}")
        out.append(f"# TYPE {self.name} gauge")
        with self._mu:
            if not self._values:
                out.append(f"{self.name} 0")
            for key, v in sorted(self._values.items()):
                lbl = ",".join(f'{n}="{val}"' for n, val in
                               zip(self.label_names, key))
                out.append(f"{self.name}{{{lbl}}} {v:g}" if lbl
                           else f"{self.name} {v:g}")


class Histogram:
    BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5, 10, float("inf"))

    def __init__(self, name: str, help_: str):
        self.name = name
        self.help = help_
        self._mu = threading.Lock()
        self._counts = [0] * len(self.BUCKETS)
        self._sum = 0.0
        self._n = 0

    def observe(self, value: float) -> None:
        with self._mu:
            self._sum += value
            self._n += 1
            for i, b in enumerate(self.BUCKETS):
                if value <= b:
                    self._counts[i] += 1

    def render(self, out: list) -> None:
        out.append(f"# HELP {self.name} {self.help}")
        out.append(f"# TYPE {self.name} histogram")
        with self._mu:
            for b, c in zip(self.BUCKETS, self._counts):
                le = "+Inf" if b == float("inf") else f"{b:g}"
                out.append(f'{self.name}_bucket{{le="{le}"}} {c}')
            out.append(f"{self.name}_sum {self._sum:g}")
            out.append(f"{self.name}_count {self._n}")


class BandwidthMonitor:
    """Per-bucket rx/tx rates over a sliding window — the bandwidth
    monitor the admin API reports (cf. cmd/admin-router.go bandwidth
    route + internal/bucket/bandwidth/monitor.go, which the reference
    uses for replication throttling and `mc admin bandwidth`)."""

    WINDOW = 10.0                    # seconds
    MAX_BUCKETS = 1024               # hostile-path cardinality bound

    def __init__(self):
        import collections
        import threading
        self._mu = threading.Lock()
        # bucket -> deque[(ts, rx, tx)]
        self._events: dict[str, object] = {}
        self._deque = collections.deque

    def record(self, bucket: str, rx: int, tx: int) -> None:
        import time as _t
        now = _t.monotonic()
        cutoff = now - self.WINDOW
        with self._mu:
            dq = self._events.get(bucket)
            if dq is None:
                if len(self._events) >= self.MAX_BUCKETS:
                    # evict idle buckets before refusing new ones
                    for name, other in list(self._events.items()):
                        while other and other[0][0] < cutoff:
                            other.popleft()
                        if not other:
                            del self._events[name]
                    if len(self._events) >= self.MAX_BUCKETS:
                        return           # saturated: drop, don't grow
                dq = self._events[bucket] = self._deque()
            dq.append((now, rx, tx))
            while dq and dq[0][0] < cutoff:
                dq.popleft()

    def report(self, buckets: list[str] | None = None) -> dict:
        import time as _t
        now = _t.monotonic()
        cutoff = now - self.WINDOW
        out = {}
        with self._mu:
            for bucket, dq in list(self._events.items()):
                while dq and dq[0][0] < cutoff:
                    dq.popleft()
                if not dq:
                    # evict idle buckets: _events must not grow with
                    # every bucket name ever requested
                    del self._events[bucket]
                    continue
                if buckets and bucket not in buckets:
                    continue
                rx = sum(e[1] for e in dq)
                tx = sum(e[2] for e in dq)
                out[bucket] = {
                    "rx_bytes_per_s": round(rx / self.WINDOW, 1),
                    "tx_bytes_per_s": round(tx / self.WINDOW, 1)}
        return out


#: Where the host's bitrot kernels hash a batch's full shard blocks
#: (`mtpu_host_hash_bytes_total{site}`).
HOST_HASH_SITES = ("get", "heal", "put")

#: Where the GET path allocates anew for a segment
#: (`mtpu_get_fresh_buffer_bytes_total{site}`), or leases an arena it
#: already holds (`mtpu_get_leased_buffer_bytes_total{site}`).
GET_FRESH_SITES = ("gather", "assemble", "join", "response", "read")


class DataPathStats:
    """Process-global heal / degraded-read data-path accounting.

    The reconstruct pipeline (engine/heal.py, ErasureSet._read_part)
    runs deep inside the engine where no MetricsRegistry instance is
    reachable — and must work without a server at all (bench, tests,
    `heal_drive` from an admin job). So the engine records into this
    singleton and the registry renders from a snapshot, the same split
    the reference makes between globalBackgroundHealState and the
    metrics collector (cmd/metrics-v2.go getHealMetrics)."""

    STAGES = ("read", "decode", "write")

    def __init__(self):
        self._mu = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._mu:
            self.heal_bytes = 0              # repaired shard bytes written
            self.heal_source_bytes = 0       # surviving shard bytes read
            self.heal_stage_s = {s: 0.0 for s in self.STAGES}
            self.heal_batches = 0
            self.heal_batch_blocks = 0       # blocks actually carried
            self.heal_batch_capacity = 0     # blocks the batches could carry
            self.heal_objects = 0
            self.degraded_reads = 0
            self.degraded_bytes = 0
            self.degraded_s = 0.0
            # Healthy-read fast path (verify-only verdicts + systematic
            # gather; on the fused host route verify_s includes the
            # gather — it is one C pass).
            self.healthy_reads = 0
            self.healthy_bytes = 0
            self.healthy_stage_s = {"read": 0.0, "verify": 0.0,
                                    "assemble": 0.0}
            self.fastpath_fallbacks = 0
            # Multipart PUT pipeline stages (encode of batch i+1
            # overlaps the shard writes of batch i, so wall time is
            # less than the stage sums).
            self.mp_batches = 0
            self.mp_bytes = 0
            self.mp_stage_s = {"encode": 0.0, "write": 0.0,
                               "complete": 0.0}
            # Cross-request dispatch coalescing (ops/coalesce.py):
            # items = per-request submissions, dispatches = kernel
            # launches, so items/dispatches is the mean batch occupancy
            # and dispatches/items the dispatches-per-request ratio.
            self.co_dispatches = 0
            self.co_items = 0
            self.co_weight = 0           # 1 MiB-block budget units
            self.co_wait_s = 0.0         # summed per-item queue wait
            # Dispatch fault containment: batch faults are coalesced
            # dispatches that raised (members then retried solo),
            # fallbacks are call sites that recomputed a span through
            # the direct reference path after a failed handle.
            self.co_batch_faults = 0
            self.co_member_retries = 0
            self.co_fallbacks = 0
            # Per-device coalescer lanes (PR 10): device index ->
            # {dispatches, items, weight, wait_s}.  Aggregates above
            # stay the cross-lane totals; this map is what the
            # mtpu_device_lane_* gauge families render from.
            self.lanes = {}
            # XLA compiles in this process (ops/devices.py listener):
            # persistent-cache hits are not counted.
            self.jit_compiles = 0
            self.jit_compile_s = 0.0
            # 1 MiB blocks whose PUT parity was computed on each plane:
            # the owning set's device lane, the SPMD mesh, the host.
            self.encode_blocks = {"lane": 0, "mesh": 0, "host": 0}
            # Full blocks whose K chosen rows a read (or a heal batch)
            # digest-verified; those of them it also rebuilt rows for;
            # and the (k, m, sources, targets) it has rebuilt from so
            # far.  Bytes a PUT copied into the zero-padded block
            # layout of a K that does not divide the block.
            self.verify_blocks = 0
            self.decode_blocks = 0
            self._decode_patterns: set[tuple] = set()
            self.stage_pad_bytes = 0
            # Bytes of framing and digest-stabilising buffers PUT
            # streams allocated anew (never the reuse).
            self.put_fresh_buffer_bytes = 0
            # Bytes of arrays and byte strings the GET path allocated
            # anew for a segment, by site (never a view, never a reuse).
            self.get_fresh_buffer_bytes = dict.fromkeys(
                GET_FRESH_SITES, 0)
            # Bytes of segment buffers leased from an arena that was
            # already mapped (engine/segarena.py), by site: the hits
            # beside the misses above.
            self.get_leased_buffer_bytes = dict.fromkeys(
                GET_FRESH_SITES, 0)
            # Shard rows a read's segment fetched, by route: batched
            # (K rows in one native call, storage/drive.read_rows) or
            # pool (a drive call a row).
            self.shard_rows_read = {"batched": 0, "pool": 0}
            # Bytes of full shard blocks the host's bitrot kernels
            # hashed, by site (a digest the chip computes: none).
            self.host_hash_bytes = dict.fromkeys(HOST_HASH_SITES, 0)
            # Episodes in which requests were in flight and none
            # completed for the stall watcher's limit (server.py).
            self.request_stall_episodes = 0
            # Cross-process dispatch (ops/ipc_dispatch.py, worker pool):
            # items shipped to the device owner, results received,
            # fallbacks (arena/ring full -> computed locally), and
            # owner-death events observed by this worker.
            self.ipc_submits = 0
            self.ipc_rows = 0
            self.ipc_results = 0
            self.ipc_fallbacks = 0
            self.ipc_owner_deaths = 0
            # Hedged shard reads (Tail-at-Scale first-k-wins): fired =
            # hedge timers that expired, spares = speculative parity
            # reads launched, wins = spare rows used in the final k.
            self.hedged_reads = 0
            self.hedge_fired = 0
            self.hedge_spares = 0
            self.hedge_wins = 0
            # Drive circuit-breaker transitions by target state.
            self.drive_transitions = {"ok": 0, "suspect": 0,
                                      "offline": 0}
            # Native digest plane (utils/digestlanes.py +
            # native/digest.cc): md5 lane-scheduler ticks and batched
            # sha256 calls.  streams/calls is the mean lane occupancy —
            # >1 means independent digest streams really are advancing
            # together through SIMD lanes.
            self.dg_md5_calls = 0
            self.dg_md5_streams = 0
            self.dg_md5_bytes = 0
            self.dg_sha_calls = 0
            self.dg_sha_bufs = 0
            self.dg_sha_bytes = 0
            # Process-lifecycle accounting: boot-time recovery sweep
            # (stale tmp entries + orphaned multipart staging removed),
            # MRF journal entries replayed into the queue on boot, and
            # graceful drains (leftover = requests still inflight when
            # MTPU_DRAIN_TIMEOUT expired).
            self.recovery_sweeps = 0
            self.recovery_tmp_entries = 0
            self.recovery_mp_stage = 0
            self.mrf_replayed = 0
            self.drains = 0
            self.drain_leftover = 0
            self.drain_s = 0.0
            # Network plane (rpc/rest.py): peer online/offline flips by
            # direction, idempotent-call retries, per-request deadline
            # budget exhaustions, and chaos-injected transport faults by
            # kind (MTPU_NETCHAOS).
            self.peer_transitions = {"online": 0, "offline": 0}
            self.rpc_retries = 0
            self.rpc_deadline_exceeded = 0
            self.netchaos_injected = {"slow": 0, "reset": 0,
                                      "blackhole": 0, "truncate": 0,
                                      "oneway": 0}
            # Zero-copy data path (PR 16, ops/zerocopy.py): hot-cache
            # GETs served as pinned arena views (no userspace body
            # copy), gather-write sendmsg responses, kernel sendfile
            # responses, vectored shard writes (pwritev batches), and
            # eligibility fallbacks to the buffered path.
            self.zerocopy_hot_views = 0
            self.zerocopy_hot_view_bytes = 0
            self.zerocopy_sendmsg = 0
            self.zerocopy_sendmsg_bytes = 0
            self.zerocopy_sendfile = 0
            self.zerocopy_sendfile_bytes = 0
            self.zerocopy_vectored_writes = 0
            self.zerocopy_vectored_write_bytes = 0
            self.zerocopy_fallbacks = 0
            # Request-body pulls (utils/streams.SocketBodyReader): one
            # fill of the caller's view from the connection, by how it
            # ran (native: one ec_recv_exact call; buffered: rfile),
            # and the recvs those pulls made.
            self.body_pulls = {"native": 0, "buffered": 0}
            self.body_pull_recvs = {"native": 0, "buffered": 0}
            # Small-object metadata plane (PR 19, ops/metalanes.py):
            # xl.meta publishes and the fsyncs paying for them (solo
            # write_metadata: 1 fsync per publish; group commit: 1
            # journal fsync amortized over the whole batch), journal
            # replays at boot, engine metadata-read requests by how
            # their all-N fan-out ran (inline: on the request's thread,
            # every drive in-process; pool: remote drives), and the
            # write lanes' scheduling stats.
            self.meta_publishes = 0
            self.meta_fsyncs = 0
            self.meta_group_commits = 0
            self.meta_group_items = 0
            self.meta_journal_replays = 0
            self.meta_read_requests = 0
            self.meta_read_fanouts = {"inline": 0, "pool": 0}
            self.meta_lane_dispatches = 0
            self.meta_lane_items = 0
            self.meta_lane_wait_s = 0.0
            self.meta_inline_ops = 0

    def record_heal_batch(self, blocks: int, capacity: int,
                          source_bytes: int, out_bytes: int,
                          read_s: float, decode_s: float,
                          write_s: float) -> None:
        with self._mu:
            self.heal_batches += 1
            self.heal_batch_blocks += blocks
            self.heal_batch_capacity += capacity
            self.heal_source_bytes += source_bytes
            self.heal_bytes += out_bytes
            self.heal_stage_s["read"] += read_s
            self.heal_stage_s["decode"] += decode_s
            self.heal_stage_s["write"] += write_s

    def record_heal_object(self) -> None:
        with self._mu:
            self.heal_objects += 1

    def record_degraded_read(self, nbytes: int, seconds: float) -> None:
        with self._mu:
            self.degraded_reads += 1
            self.degraded_bytes += nbytes
            self.degraded_s += seconds

    def record_healthy_read(self, nbytes: int, read_s: float,
                            verify_s: float, assemble_s: float) -> None:
        with self._mu:
            self.healthy_reads += 1
            self.healthy_bytes += nbytes
            self.healthy_stage_s["read"] += read_s
            self.healthy_stage_s["verify"] += verify_s
            self.healthy_stage_s["assemble"] += assemble_s

    def record_fastpath_fallback(self) -> None:
        with self._mu:
            self.fastpath_fallbacks += 1

    def record_mp_batch(self, nbytes: int, encode_s: float,
                        write_s: float) -> None:
        with self._mu:
            self.mp_batches += 1
            self.mp_bytes += nbytes
            self.mp_stage_s["encode"] += encode_s
            self.mp_stage_s["write"] += write_s

    def record_mp_complete(self, seconds: float) -> None:
        with self._mu:
            self.mp_stage_s["complete"] += seconds

    def record_coalesce_dispatch(self, items: int, weight: int,
                                 wait_s: float) -> None:
        with self._mu:
            self.co_dispatches += 1
            self.co_items += items
            self.co_weight += weight
            self.co_wait_s += wait_s

    def record_lane_dispatch(self, device: int, items: int, weight: int,
                             wait_s: float, rows: int = 0,
                             padded_rows: int = 0) -> None:
        """One coalesced launch on device lane `device`: `rows` of
        work in a batch padded to `padded_rows`."""
        with self._mu:
            row = self.lanes.get(device)
            if row is None:
                row = self.lanes[device] = {
                    "dispatches": 0, "items": 0, "weight": 0,
                    "wait_s": 0.0, "rows": 0, "padded_rows": 0}
            row["dispatches"] += 1
            row["items"] += items
            row["weight"] += weight
            row["wait_s"] += wait_s
            row["rows"] += rows
            row["padded_rows"] += padded_rows

    def record_jit_compile(self, seconds: float) -> None:
        with self._mu:
            self.jit_compiles += 1
            self.jit_compile_s += seconds

    def record_encode_blocks(self, plane: str, blocks: int) -> None:
        """`blocks` full 1 MiB blocks of a PUT went to `plane` ("lane",
        "mesh" or "host") for their parity (engine/shardmath.py)."""
        with self._mu:
            self.encode_blocks[plane] += blocks

    def record_verify_blocks(self, blocks: int,
                             pattern: tuple | None = None) -> None:
        """`blocks` full blocks had their K chosen rows verified
        (engine/erasure_set.py: the healthy read; engine/shardmath.py:
        `verify_transform`); `pattern` = (k, m, sources, targets) where
        rows were rebuilt from them in the same pass."""
        with self._mu:
            self.verify_blocks += blocks
            if pattern is not None:
                self.decode_blocks += blocks
                self._decode_patterns.add(pattern)

    def record_stage_pad(self, nbytes: int) -> None:
        """`nbytes` of a PUT body were copied into the zero-padded
        block layout (engine/erasure_set.py: `engine.stage`)."""
        with self._mu:
            self.stage_pad_bytes += nbytes

    def record_put_fresh_buffer(self, nbytes: int) -> None:
        """A PUT stream allocated `nbytes` anew for a per-batch buffer:
        a framing buffer's first acquisition or growth
        (engine/shardmath.py), a copy that stabilises a digest piece
        (utils/streams.py, utils/digestlanes.py).  Reuse never
        counts."""
        with self._mu:
            self.put_fresh_buffer_bytes += nbytes

    def record_get_fresh_buffer(self, site: str, nbytes: int) -> None:
        """The GET path allocated `nbytes` anew for a segment at `site`
        (GET_FRESH_SITES; engine/erasure_set.py): an arena the segment
        pool had to map for the gather's `x`, the assembled `y`, the
        join or the shard rows read in one native call
        (engine/segarena.py), a tail's concatenation, the response's
        bytearray.  A view costs nothing and is not counted,
        nor is a lease of an arena already mapped
        (`record_get_leased_buffer`); the host arrays the runtime fills
        on a result's way back are `mtpu_d2h_bytes_total`'s."""
        if nbytes:
            with self._mu:
                self.get_fresh_buffer_bytes[site] += nbytes

    def record_get_leased_buffer(self, site: str, nbytes: int) -> None:
        """The GET path leased `nbytes` at `site` from an arena the
        segment pool already held: nothing was mapped."""
        with self._mu:
            self.get_leased_buffer_bytes[site] += nbytes

    def record_shard_rows(self, path: str, n: int) -> None:
        """`n` shard rows of a read's segment came back on `path`
        ("batched" or "pool")."""
        with self._mu:
            self.shard_rows_read[path] += n

    def record_host_hash(self, site: str, nbytes: int) -> None:
        """The host's bitrot kernels hashed `nbytes` of full shard
        blocks at `site` (HOST_HASH_SITES): a read's or a heal's K
        chosen rows, a PUT's or a heal's framed rows."""
        with self._mu:
            self.host_hash_bytes[site] += nbytes

    def record_request_stall(self) -> None:
        with self._mu:
            self.request_stall_episodes += 1

    def record_co_fault(self, members: int) -> None:
        """A coalesced dispatch raised; `members` spans were retried
        individually (0 = single-item dispatch, nothing to contain)."""
        with self._mu:
            self.co_batch_faults += 1
            self.co_member_retries += members

    def record_co_fallback(self) -> None:
        with self._mu:
            self.co_fallbacks += 1

    def record_ipc_submit(self, rows: int = 0) -> None:
        with self._mu:
            self.ipc_submits += 1
            self.ipc_rows += rows

    def record_ipc_result(self) -> None:
        with self._mu:
            self.ipc_results += 1

    def record_ipc_fallback(self) -> None:
        with self._mu:
            self.ipc_fallbacks += 1

    def record_ipc_owner_death(self) -> None:
        with self._mu:
            self.ipc_owner_deaths += 1

    def record_hedge(self, fired: bool, spares: int, wins: int) -> None:
        with self._mu:
            self.hedged_reads += 1
            if fired:
                self.hedge_fired += 1
            self.hedge_spares += spares
            self.hedge_wins += wins

    def record_drive_transition(self, to_state: str) -> None:
        with self._mu:
            if to_state in self.drive_transitions:
                self.drive_transitions[to_state] += 1

    def record_digest_batch(self, streams: int, nbytes: int) -> None:
        """One md5 lane-scheduler tick advanced `streams` streams by a
        total of `nbytes` in a single native call."""
        with self._mu:
            self.dg_md5_calls += 1
            self.dg_md5_streams += streams
            self.dg_md5_bytes += nbytes

    def record_sha_batch(self, bufs: int, nbytes: int) -> None:
        with self._mu:
            self.dg_sha_calls += 1
            self.dg_sha_bufs += bufs
            self.dg_sha_bytes += nbytes

    def record_recovery_sweep(self, tmp_entries: int,
                              mp_stage: int) -> None:
        """One drive's boot-time sweep of dead-epoch state."""
        with self._mu:
            self.recovery_sweeps += 1
            self.recovery_tmp_entries += tmp_entries
            self.recovery_mp_stage += mp_stage

    def record_mrf_replay(self, entries: int) -> None:
        with self._mu:
            self.mrf_replayed += entries

    def record_drain(self, leftover: int, seconds: float) -> None:
        with self._mu:
            self.drains += 1
            self.drain_leftover += leftover
            self.drain_s += seconds

    def record_peer_transition(self, online: bool) -> None:
        with self._mu:
            self.peer_transitions["online" if online else "offline"] += 1

    def record_rpc_retry(self) -> None:
        with self._mu:
            self.rpc_retries += 1

    def record_rpc_deadline_exceeded(self) -> None:
        with self._mu:
            self.rpc_deadline_exceeded += 1

    def record_netchaos(self, kind: str) -> None:
        with self._mu:
            if kind in self.netchaos_injected:
                self.netchaos_injected[kind] += 1

    def record_zerocopy_hot_view(self, nbytes: int) -> None:
        """One hot-cache GET answered with a pinned arena view (the
        body never crossed into a userspace copy)."""
        with self._mu:
            self.zerocopy_hot_views += 1
            self.zerocopy_hot_view_bytes += nbytes

    def record_zerocopy_send(self, kind: str, nbytes: int) -> None:
        """One response body shipped by the zero-copy writer; `kind`
        is "sendmsg" (gather) or "sendfile" (kernel file send)."""
        with self._mu:
            if kind == "sendfile":
                self.zerocopy_sendfile += 1
                self.zerocopy_sendfile_bytes += nbytes
            else:
                self.zerocopy_sendmsg += 1
                self.zerocopy_sendmsg_bytes += nbytes

    def record_zerocopy_vectored_write(self, nbytes: int) -> None:
        """One pwritev-batched shard append (all stripes of one shard
        in a single vectored syscall)."""
        with self._mu:
            self.zerocopy_vectored_writes += 1
            self.zerocopy_vectored_write_bytes += nbytes

    def record_zerocopy_fallback(self) -> None:
        """A response that was eligible-looking but fell back to the
        buffered writer (TLS socket, chunked framing, flag off at send
        time)."""
        with self._mu:
            self.zerocopy_fallbacks += 1

    def record_body_pull(self, path: str, recvs: int) -> None:
        """One pull of a request body from its connection on `path`
        ("native" or "buffered"), which made `recvs` recvs."""
        with self._mu:
            self.body_pulls[path] += 1
            self.body_pull_recvs[path] += recvs

    def record_meta_publish(self) -> None:
        """One solo xl.meta publish (drive.write_metadata): one
        fsynced rename-into-place, one fsync."""
        with self._mu:
            self.meta_publishes += 1
            self.meta_fsyncs += 1

    def record_meta_group_commit(self, n: int) -> None:
        """One group-committed metadata batch
        (drive.write_metadata_many): n publishes sharing a single
        journal fsync."""
        with self._mu:
            self.meta_group_commits += 1
            self.meta_group_items += n
            self.meta_publishes += n
            self.meta_fsyncs += 1

    def record_meta_journal_replay(self, n: int) -> None:
        with self._mu:
            self.meta_journal_replays += n

    def record_meta_read_request(self, path: str) -> None:
        """One engine-level metadata read (_read_metadata call) whose
        all-N fan-out ran on `path` ("inline": the request's own
        thread; "pool": the drive pool)."""
        with self._mu:
            self.meta_read_requests += 1
            self.meta_read_fanouts[path] += 1

    def record_meta_lane_dispatch(self, items: int,
                                  wait_s: float) -> None:
        with self._mu:
            self.meta_lane_dispatches += 1
            self.meta_lane_items += items
            self.meta_lane_wait_s += wait_s

    def record_meta_inline_op(self) -> None:
        """A lane submit that ran on the caller's thread (idle fast
        path or broken-dispatcher degradation)."""
        with self._mu:
            self.meta_inline_ops += 1

    def snapshot(self) -> dict:
        with self._mu:
            return {
                "heal_bytes": self.heal_bytes,
                "heal_source_bytes": self.heal_source_bytes,
                "heal_stage_s": dict(self.heal_stage_s),
                "heal_batches": self.heal_batches,
                "heal_batch_blocks": self.heal_batch_blocks,
                "heal_batch_capacity": self.heal_batch_capacity,
                "heal_batch_occupancy": (
                    self.heal_batch_blocks / self.heal_batch_capacity
                    if self.heal_batch_capacity else 0.0),
                "heal_objects": self.heal_objects,
                "degraded_reads": self.degraded_reads,
                "degraded_bytes": self.degraded_bytes,
                "degraded_seconds": self.degraded_s,
                "healthy_reads": self.healthy_reads,
                "healthy_bytes": self.healthy_bytes,
                "healthy_stage_s": dict(self.healthy_stage_s),
                "fastpath_fallbacks": self.fastpath_fallbacks,
                "mp_batches": self.mp_batches,
                "mp_bytes": self.mp_bytes,
                "mp_stage_s": dict(self.mp_stage_s),
                "co_dispatches": self.co_dispatches,
                "co_items": self.co_items,
                "co_weight": self.co_weight,
                "co_wait_s": self.co_wait_s,
                "co_occupancy": (self.co_items / self.co_dispatches
                                 if self.co_dispatches else 0.0),
                "co_dispatches_per_item": (
                    self.co_dispatches / self.co_items
                    if self.co_items else 0.0),
                "co_batch_faults": self.co_batch_faults,
                "co_member_retries": self.co_member_retries,
                "co_fallbacks": self.co_fallbacks,
                "lanes": {d: dict(row)
                          for d, row in sorted(self.lanes.items())},
                "jit_compiles": self.jit_compiles,
                "jit_compile_s": self.jit_compile_s,
                "encode_blocks": dict(self.encode_blocks),
                "verify_blocks": self.verify_blocks,
                "decode_blocks": self.decode_blocks,
                "decode_patterns": len(self._decode_patterns),
                "stage_pad_bytes": self.stage_pad_bytes,
                "put_fresh_buffer_bytes": self.put_fresh_buffer_bytes,
                "get_fresh_buffer_bytes": dict(
                    self.get_fresh_buffer_bytes),
                "get_leased_buffer_bytes": dict(
                    self.get_leased_buffer_bytes),
                "shard_rows_read": dict(self.shard_rows_read),
                "host_hash_bytes": dict(self.host_hash_bytes),
                "request_stall_episodes": self.request_stall_episodes,
                "ipc_submits": self.ipc_submits,
                "ipc_rows": self.ipc_rows,
                "ipc_results": self.ipc_results,
                "ipc_fallbacks": self.ipc_fallbacks,
                "ipc_owner_deaths": self.ipc_owner_deaths,
                "hedged_reads": self.hedged_reads,
                "hedge_fired": self.hedge_fired,
                "hedge_spares": self.hedge_spares,
                "hedge_wins": self.hedge_wins,
                "drive_transitions": dict(self.drive_transitions),
                "dg_md5_calls": self.dg_md5_calls,
                "dg_md5_streams": self.dg_md5_streams,
                "dg_md5_bytes": self.dg_md5_bytes,
                "dg_md5_occupancy": (
                    self.dg_md5_streams / self.dg_md5_calls
                    if self.dg_md5_calls else 0.0),
                "dg_sha_calls": self.dg_sha_calls,
                "dg_sha_bufs": self.dg_sha_bufs,
                "dg_sha_bytes": self.dg_sha_bytes,
                "recovery_sweeps": self.recovery_sweeps,
                "recovery_tmp_entries": self.recovery_tmp_entries,
                "recovery_mp_stage": self.recovery_mp_stage,
                "mrf_replayed": self.mrf_replayed,
                "drains": self.drains,
                "drain_leftover": self.drain_leftover,
                "drain_seconds": self.drain_s,
                "peer_transitions": dict(self.peer_transitions),
                "rpc_retries": self.rpc_retries,
                "rpc_deadline_exceeded": self.rpc_deadline_exceeded,
                "netchaos_injected": dict(self.netchaos_injected),
                "zerocopy_hot_views": self.zerocopy_hot_views,
                "zerocopy_hot_view_bytes": self.zerocopy_hot_view_bytes,
                "zerocopy_sendmsg": self.zerocopy_sendmsg,
                "zerocopy_sendmsg_bytes": self.zerocopy_sendmsg_bytes,
                "zerocopy_sendfile": self.zerocopy_sendfile,
                "zerocopy_sendfile_bytes": self.zerocopy_sendfile_bytes,
                "zerocopy_vectored_writes": self.zerocopy_vectored_writes,
                "zerocopy_vectored_write_bytes":
                    self.zerocopy_vectored_write_bytes,
                "zerocopy_fallbacks": self.zerocopy_fallbacks,
                "body_pulls": dict(self.body_pulls),
                "body_pull_recvs": dict(self.body_pull_recvs),
                "meta_publishes": self.meta_publishes,
                "meta_fsyncs": self.meta_fsyncs,
                "meta_group_commits": self.meta_group_commits,
                "meta_group_items": self.meta_group_items,
                "meta_batch_occupancy": (
                    self.meta_group_items / self.meta_group_commits
                    if self.meta_group_commits else 0.0),
                "meta_fsyncs_per_object": (
                    self.meta_fsyncs / self.meta_publishes
                    if self.meta_publishes else 0.0),
                "meta_journal_replays": self.meta_journal_replays,
                "meta_read_requests": self.meta_read_requests,
                "meta_read_fanouts": dict(self.meta_read_fanouts),
                "meta_lane_dispatches": self.meta_lane_dispatches,
                "meta_lane_items": self.meta_lane_items,
                "meta_lane_wait_s": self.meta_lane_wait_s,
                "meta_inline_ops": self.meta_inline_ops,
            }


#: Engine-side singleton (see DataPathStats docstring).
DATA_PATH = DataPathStats()


class MetricsRegistry:
    def __init__(self):
        self.api_requests = Counter(
            "mtpu_s3_requests_total", "S3 requests by API and status",
            ("api", "status"))
        self.api_errors = Counter(
            "mtpu_s3_errors_total", "S3 error responses by code", ("code",))
        self.inflight = Gauge(
            "mtpu_s3_requests_inflight", "Requests currently being served")
        self.latency = Histogram(
            "mtpu_s3_ttfb_seconds", "Request latency seconds")
        self.bytes_rx = Counter("mtpu_s3_rx_bytes_total",
                                "Bytes received from clients")
        self.bytes_tx = Counter("mtpu_s3_tx_bytes_total",
                                "Bytes sent to clients")
        self.bucket_usage = Gauge("mtpu_bucket_usage_total_bytes",
                                  "Bucket usage from last scan", ("bucket",))
        self.bucket_objects = Gauge("mtpu_bucket_objects",
                                    "Object count from last scan",
                                    ("bucket",))
        self.heal_total = Counter("mtpu_heal_objects_healed_total",
                                  "Objects healed")
        # Reconstruct-pipeline families (rendered from DATA_PATH):
        # throughput, per-stage latency, and batch occupancy for heal
        # and the degraded-read path.
        self.heal_bytes = Gauge("mtpu_heal_repaired_bytes_total",
                                "Repaired shard bytes written by heal")
        self.heal_source_bytes = Gauge(
            "mtpu_heal_source_bytes_total",
            "Surviving shard bytes read by heal")
        self.heal_stage_seconds = Gauge(
            "mtpu_heal_stage_seconds_total",
            "Heal pipeline time by stage", ("stage",))
        self.heal_batches = Gauge("mtpu_heal_batches_total",
                                  "Reconstruct batches dispatched by heal")
        self.heal_batch_occupancy = Gauge(
            "mtpu_heal_batch_occupancy_ratio",
            "Blocks carried / batch capacity (1.0 = full batches)")
        self.degraded_reads = Gauge("mtpu_degraded_reads_total",
                                    "GET segments served by reconstruction")
        self.degraded_bytes = Gauge(
            "mtpu_degraded_read_bytes_total",
            "Bytes served through the degraded-read path")
        self.degraded_seconds = Gauge(
            "mtpu_degraded_read_seconds_total",
            "Time spent reconstructing degraded reads")
        # Healthy-read fast-path families: verify-only verdicts +
        # systematic assembly, zero GF(2^8) work (MTPU_GET_FASTPATH).
        self.healthy_reads = Gauge(
            "mtpu_healthy_reads_total",
            "GET segments served by the verify-only fast path")
        self.healthy_bytes = Gauge(
            "mtpu_healthy_read_bytes_total",
            "Bytes served through the verify-only fast path")
        self.healthy_stage_seconds = Gauge(
            "mtpu_healthy_read_stage_seconds_total",
            "Healthy-read fast path time by stage", ("stage",))
        self.fastpath_fallbacks = Gauge(
            "mtpu_get_fastpath_fallbacks_total",
            "Fast-path reads that fell back to verify+decode")
        # Multipart PUT pipeline families.
        self.mp_batches = Gauge(
            "mtpu_multipart_put_batches_total",
            "Encode batches through the multipart PUT pipeline")
        self.mp_bytes = Gauge(
            "mtpu_multipart_put_bytes_total",
            "Part bytes through the multipart PUT pipeline")
        self.mp_stage_seconds = Gauge(
            "mtpu_multipart_put_stage_seconds_total",
            "Multipart PUT pipeline time by stage", ("stage",))
        # Cross-request dispatch-coalescing families (MTPU_COALESCE).
        self.co_dispatches = Gauge(
            "mtpu_coalesce_dispatches_total",
            "Coalesced kernel launches")
        self.co_items = Gauge(
            "mtpu_coalesce_items_total",
            "Work items submitted to the dispatch coalescer")
        self.co_blocks = Gauge(
            "mtpu_coalesce_block_weight_total",
            "Summed work-item weight through coalesced dispatches "
            "(1 MiB-block units)")
        self.co_occupancy = Gauge(
            "mtpu_coalesce_batch_occupancy_items",
            "Mean work items per coalesced dispatch (>1 = cross-request "
            "batching is happening)")
        self.co_wait_seconds = Gauge(
            "mtpu_coalesce_queue_wait_seconds_total",
            "Summed per-item queue wait before dispatch")
        # Dispatch fault-containment families (PR 5).
        self.co_batch_faults = Gauge(
            "mtpu_coalesce_batch_faults_total",
            "Coalesced dispatches that raised and were retried "
            "member-by-member")
        self.co_member_retries = Gauge(
            "mtpu_coalesce_member_retries_total",
            "Batch member spans retried individually after a fault")
        self.co_fallbacks = Gauge(
            "mtpu_coalesce_fallbacks_total",
            "Call sites that recomputed a span through the direct "
            "path after a failed coalesced handle")
        # Per-device coalescer-lane families (PR 10): one series per
        # device lane, so skew between lanes is visible (a pinned
        # keyspace lights one device; spread lights them all).
        self.device_lane_dispatches = Gauge(
            "mtpu_device_lane_dispatches_total",
            "Coalesced kernel launches per device lane", ("device",))
        self.device_lane_occupancy = Gauge(
            "mtpu_device_lane_occupancy",
            "Mean work items per dispatch on this device lane",
            ("device",))
        self.device_lane_queue_wait = Gauge(
            "mtpu_device_lane_queue_wait_seconds_total",
            "Summed per-item queue wait before dispatch on this "
            "device lane", ("device",))
        self.device_lane_rows = Gauge(
            "mtpu_device_lane_rows_total",
            "Rows of work the lane's dispatches carried (axis 0 of "
            "the batch: blocks of an encode, shard rows of a digest)",
            ("lane",))
        self.device_lane_padded_rows = Gauge(
            "mtpu_device_lane_padded_rows_total",
            "Rows the lane's dispatches ran at, their step of the "
            "shape ladder; pad share = 1 - rows / padded rows",
            ("lane",))
        self.device_lane_state_seconds = Gauge(
            "mtpu_device_lane_state_seconds_total",
            "Wall time of a device lane since it was made, by state "
            "(no_work, linger, pack, h2d, launch, device_wait; they "
            "sum to the lane's age)", ("lane", "state"))
        self.jit_compiles = Gauge(
            "mtpu_jit_compiles_total",
            "XLA compilations in this process (persistent-cache hits "
            "are not counted)")
        self.jit_compile_seconds = Gauge(
            "mtpu_jit_compile_seconds_total",
            "Seconds spent in XLA compilations in this process")
        self.encode_blocks = Gauge(
            "mtpu_encode_blocks_total",
            "1 MiB blocks of PUT bodies whose parity was computed on "
            "this plane: lane (the owning set's device), mesh (SPMD "
            "over all chips), host", ("plane",))
        self.verify_blocks = Gauge(
            "mtpu_verify_blocks_total",
            "Full blocks whose K chosen shard rows a read or a heal "
            "batch digest-verified, on any plane")
        self.decode_blocks = Gauge(
            "mtpu_decode_blocks_total",
            "Those of mtpu_verify_blocks_total that had rows rebuilt "
            "from the K verified ones in the same pass")
        self.decode_patterns = Gauge(
            "mtpu_decode_patterns_total",
            "Distinct (k, m, sources, targets) rows were rebuilt for "
            "so far; on a device lane they share one program a (k, m)")
        self.stage_pad_bytes = Gauge(
            "mtpu_stage_pad_bytes_total",
            "Bytes of PUT bodies copied into the zero-padded block "
            "layout (engine.stage: a K that does not divide 1 MiB)")
        self.put_fresh_buffer_bytes = Gauge(
            "mtpu_put_fresh_buffer_bytes_total",
            "Bytes of per-batch framing and digest-stabilising buffers "
            "PUT streams allocated anew (first use, growth, a copy of "
            "a digest piece); 0 while every batch reuses what its "
            "thread and its ring already hold")
        self.get_fresh_buffer_bytes = Gauge(
            "mtpu_get_fresh_buffer_bytes_total",
            "Bytes the GET path allocated anew for a segment, by site: "
            "gather (the K chosen rows into x), assemble (y, a tail's "
            "concatenation), join (the pieces copied into one range), "
            "response (the object's bytearray), read (the buffer the "
            "shard rows are read into); an arena the segment "
            "pool had to map counts here, a view or a lease of one "
            "already mapped does not",
            ("site",))
        self.get_leased_buffer_bytes = Gauge(
            "mtpu_get_leased_buffer_bytes_total",
            "Bytes of segment buffers the GET path leased from an arena "
            "already mapped, by site: the hits beside "
            "mtpu_get_fresh_buffer_bytes_total's misses",
            ("site",))
        self.shard_rows_read = Gauge(
            "mtpu_shard_rows_read_total",
            "Shard rows a read's segment fetched, by route: batched "
            "(K rows in one native call, the GIL released once) or pool "
            "(a drive call a row: remote drives, the host-fused plane, "
            "O_DIRECT, no native library)", ("path",))
        self.host_hash_bytes = Gauge(
            "mtpu_host_hash_bytes_total",
            "Bytes of full shard blocks the host's bitrot kernels hashed, "
            "by site: get (a read's K chosen rows), heal (a heal batch's "
            "K rows and the rows it frames), put (a PUT batch's K+M "
            "framed rows); a digest the chip computes counts nowhere",
            ("site",))
        self.get_arena_free_bytes = Gauge(
            "mtpu_get_arena_free_bytes",
            "Bytes of segment arenas on the pool's free list (mapped, "
            "leased to nobody; at most engine/segarena.FREE_CAP_BYTES)")
        self.request_stall_episodes = Gauge(
            "mtpu_request_stall_episodes_total",
            "Episodes in which requests were in flight and for 3 s none "
            "began or completed, no streamed response handed on a "
            "chunk and no request body was pulled; each wrote every "
            "thread's stack, the lanes' states and MemAvailable to the "
            "server's log once")
        # Cross-process dispatch families (worker pool, PR 9).
        self.ipc_submits = Gauge(
            "mtpu_ipc_dispatch_submits_total",
            "Work items shipped to the device-owner process")
        self.ipc_results = Gauge(
            "mtpu_ipc_dispatch_results_total",
            "Remote dispatch results received back")
        self.ipc_fallbacks = Gauge(
            "mtpu_ipc_dispatch_fallbacks_total",
            "Remote submits that degraded to worker-local compute "
            "(arena/ring backpressure or owner loss)")
        self.ipc_owner_deaths = Gauge(
            "mtpu_ipc_owner_deaths_total",
            "Device-owner heartbeat losses observed by this worker")
        # Hedged shard-read families (MTPU_HEDGE).
        self.hedged_reads = Gauge(
            "mtpu_hedged_reads_total",
            "Stripe reads gathered through the first-k-wins path")
        self.hedge_fired = Gauge(
            "mtpu_hedge_timers_fired_total",
            "Hedge delays that expired (stragglers covered by spares)")
        self.hedge_spares = Gauge(
            "mtpu_hedge_spare_reads_total",
            "Speculative parity-shard reads launched")
        self.hedge_wins = Gauge(
            "mtpu_hedge_wins_total",
            "Hedged spare rows that made the final k")
        # Native digest-plane families (MTPU_NATIVE_DIGEST).
        self.dg_md5_calls = Gauge(
            "mtpu_digest_md5_lane_calls_total",
            "Native multi-buffer MD5 lane-scheduler ticks")
        self.dg_md5_streams = Gauge(
            "mtpu_digest_md5_streams_total",
            "Streams advanced across MD5 lane-scheduler ticks")
        self.dg_md5_bytes = Gauge(
            "mtpu_digest_md5_bytes_total",
            "Bytes hashed through native MD5 lanes")
        self.dg_md5_occupancy = Gauge(
            "mtpu_digest_md5_lane_occupancy_streams",
            "Mean streams per MD5 lane tick (>1 = lanes are shared)")
        self.dg_sha_calls = Gauge(
            "mtpu_digest_sha256_batch_calls_total",
            "Batched native SHA256 calls")
        self.dg_sha_bufs = Gauge(
            "mtpu_digest_sha256_buffers_total",
            "Buffers verified through batched native SHA256")
        self.dg_sha_bytes = Gauge(
            "mtpu_digest_sha256_bytes_total",
            "Bytes hashed through batched native SHA256")
        # Drive circuit-breaker state (0=ok 1=suspect 2=offline) and
        # lifetime transitions by target state.
        self.drive_state = Gauge(
            "mtpu_drive_state",
            "Per-drive breaker state: 0 ok, 1 suspect, 2 offline",
            ("pool", "set", "drive"))
        self.drive_transitions = Gauge(
            "mtpu_drive_state_transitions_total",
            "Breaker state transitions by target state", ("state",))
        # Process-lifecycle families: boot recovery sweep + graceful
        # drain (cmd/prepare-storage.go / cmd/signals.go analogues).
        self.recovery_sweeps = Gauge(
            "mtpu_recovery_drive_sweeps_total",
            "Per-drive boot-time recovery sweeps run")
        self.recovery_tmp = Gauge(
            "mtpu_recovery_tmp_entries_swept_total",
            "Stale tmp/trash entries removed at boot")
        self.recovery_mp_stage = Gauge(
            "mtpu_recovery_multipart_stage_swept_total",
            "Orphaned multipart staging files removed at boot")
        self.mrf_replayed = Gauge(
            "mtpu_mrf_journal_replayed_total",
            "MRF journal entries replayed into the queue on boot")
        self.drains = Gauge(
            "mtpu_drains_total", "Graceful drains started")
        self.drain_leftover = Gauge(
            "mtpu_drain_leftover_requests_total",
            "Requests still inflight when the drain timeout expired")
        self.drain_seconds = Gauge(
            "mtpu_drain_seconds_total", "Time spent draining")
        # MRF heal-queue families.
        self.mrf_pending = Gauge(
            "mtpu_mrf_pending", "Objects queued for MRF heal")
        self.mrf_healed = Gauge(
            "mtpu_mrf_healed_total", "Objects healed off the MRF queue")
        self.mrf_dropped = Gauge(
            "mtpu_mrf_dropped_total",
            "MRF entries dropped (attempts exhausted or queue shed)")
        self.mrf_retries = Gauge(
            "mtpu_mrf_retries_total", "Failed MRF heal attempts")
        # Span-aggregate families (rendered from observe.span TRACER):
        # per-API traced-request percentiles + per-stage span histograms
        # ("le" carries the cumulative bucket bound in ms).
        self.trace_api_count = Gauge(
            "mtpu_trace_api_requests_total",
            "Traced requests by API (span roots)", ("api",))
        self.trace_api_errors = Gauge(
            "mtpu_trace_api_errors_total",
            "Traced error requests by API", ("api",))
        self.trace_api_latency = Gauge(
            "mtpu_trace_api_latency_ms",
            "Traced request latency percentiles in ms",
            ("api", "quantile"))
        self.trace_stage_ms = Gauge(
            "mtpu_trace_stage_ms_total",
            "Summed span time by API and stage in ms", ("api", "stage"))
        self.trace_stage_self_ms = Gauge(
            "mtpu_trace_stage_self_ms_total",
            "Summed span self time (duration minus the union of its "
            "children) by API, stage and layer in ms; a request "
            "root's own is stage http.other",
            ("api", "stage", "layer"))
        self.trace_stage_self_cpu_ms = Gauge(
            "mtpu_trace_stage_self_cpu_ms_total",
            "The part of mtpu_trace_stage_self_ms_total in which the "
            "thread ran: the thread-CPU clock of the stage's spans that "
            "read it (roots, pool hops, layer changes, "
            "span.CPU_STAGES), less that of the clocked spans below on "
            "the same thread; a stage that reads none has its time in "
            "the figure of the stage above it, so sum over a layer",
            ("api", "stage", "layer"))
        self.trace_stage_self_wait_ms = Gauge(
            "mtpu_trace_stage_self_wait_ms_total",
            "The part of mtpu_trace_stage_self_ms_total in which the "
            "thread did not run (slept, queued for the GIL or a lock): "
            "the self time the stage's CPU reading covers less that "
            "CPU, floored on the stage's sums, not a span at a time; "
            "per layer cpu + wait = self where every reading exists",
            ("api", "stage", "layer"))
        self.trace_stage_count = Gauge(
            "mtpu_trace_stage_spans_total",
            "Span count by API and stage", ("api", "stage"))
        self.trace_stage_hist = Gauge(
            "mtpu_trace_stage_duration_ms_bucket",
            "Cumulative span duration histogram by API and stage",
            ("api", "stage", "le"))
        self.drive_online = Gauge("mtpu_cluster_drives_online",
                                  "Online drives")
        self.drive_offline = Gauge("mtpu_cluster_drives_offline",
                                   "Offline drives")
        # Peer-liveness families (rpc/rest.py RPCClient accounting,
        # cf. the reference's internode health checker): per-endpoint
        # state/flap-count/staleness plus fleet-wide flip, retry,
        # deadline-exhaustion and chaos-injection counters.
        self.peer_state = Gauge(
            "mtpu_peer_state",
            "Peer RPC endpoint state: 1 online, 0 offline",
            ("endpoint",))
        self.peer_transitions = Gauge(
            "mtpu_peer_transitions_total",
            "Peer online/offline transitions", ("endpoint",))
        self.peer_last_seen = Gauge(
            "mtpu_peer_last_seen_seconds",
            "Seconds since the peer last answered an RPC "
            "(-1: never)", ("endpoint",))
        self.peer_rpc_timeout = Gauge(
            "mtpu_peer_rpc_timeout_seconds",
            "Adaptive per-call RPC deadline for the peer",
            ("endpoint",))
        self.peer_flaps = Gauge(
            "mtpu_peer_flaps_total",
            "Peer state flips across all endpoints by direction",
            ("state",))
        self.rpc_retries = Gauge(
            "mtpu_rpc_retries_total",
            "Idempotent RPC retries after retryable transport faults")
        self.rpc_deadline_exceeded = Gauge(
            "mtpu_rpc_deadline_exceeded_total",
            "RPCs aborted because the request deadline budget ran out")
        self.netchaos_injected = Gauge(
            "mtpu_netchaos_injected_total",
            "Chaos-injected transport faults by kind (MTPU_NETCHAOS)",
            ("kind",))
        # Disk-cache gauges (cf. getCacheMetrics, cmd/metrics-v2.go)
        self.cache_hits = Gauge("mtpu_cache_hits_total",
                                "Disk cache hits")
        self.cache_misses = Gauge("mtpu_cache_misses_total",
                                  "Disk cache misses")
        self.cache_evictions = Gauge("mtpu_cache_evicted_total",
                                     "Disk cache LRU evictions")
        self.cache_usage = Gauge("mtpu_cache_usage_bytes",
                                 "Disk cache bytes in use")
        self.cache_max = Gauge("mtpu_cache_total_bytes",
                               "Disk cache size budget")
        # RAM hot-object tier (engine/hotcache.py; cf. the reference's
        # cmd/disk-cache*.go tier, here shared-memory + pool-shared).
        self.hotcache_hits = Gauge("mtpu_hotcache_hits_total",
                                   "Hot-object cache body hits")
        self.hotcache_misses = Gauge("mtpu_hotcache_misses_total",
                                     "Hot-object cache misses")
        self.hotcache_meta_hits = Gauge(
            "mtpu_hotcache_meta_hits_total",
            "Hot-object cache metadata-only (HEAD/conditional) hits")
        self.hotcache_ratio = Gauge("mtpu_hotcache_hit_ratio",
                                    "Hot-object cache hit ratio")
        self.hotcache_fills = Gauge("mtpu_hotcache_fills_total",
                                    "Verified reads admitted to the "
                                    "hot cache")
        self.hotcache_evictions = Gauge(
            "mtpu_hotcache_evictions_total",
            "Hot-cache CLOCK evictions")
        self.hotcache_bypassed = Gauge(
            "mtpu_hotcache_bypassed_total",
            "Reads that bypassed fill (degraded/oversize/ineligible)")
        self.hotcache_stale = Gauge(
            "mtpu_hotcache_stale_generation_total",
            "Lookups/fills dropped on a stale bucket generation")
        self.hotcache_invalidations = Gauge(
            "mtpu_hotcache_invalidations_total",
            "Bucket-generation bumps from mutation paths")
        self.hotcache_entries = Gauge("mtpu_hotcache_entries",
                                      "Live hot-cache entries")
        self.hotcache_bytes = Gauge("mtpu_hotcache_usage_bytes",
                                    "Hot-cache body bytes cached")
        self.hotcache_segment = Gauge("mtpu_hotcache_total_bytes",
                                      "Hot-cache shared-segment size")
        # Zero-copy data path (ops/zerocopy.py + ops/bpool.py; cf.
        # internal/bpool/bpool.go and the xl-storage O_DIRECT write
        # contract).  Synced from DATA_PATH / ops.bpool.stats().
        self.zerocopy_hot_views = Gauge(
            "mtpu_zerocopy_hot_views_total",
            "Hot-cache GETs served as pinned arena views (no body copy)")
        self.zerocopy_hot_view_bytes = Gauge(
            "mtpu_zerocopy_hot_view_bytes_total",
            "Body bytes served straight from pinned arena views")
        self.zerocopy_sendmsg = Gauge(
            "mtpu_zerocopy_sendmsg_total",
            "Responses shipped by gather-write sendmsg")
        self.zerocopy_sendmsg_bytes = Gauge(
            "mtpu_zerocopy_sendmsg_bytes_total",
            "Body bytes shipped by gather-write sendmsg")
        self.zerocopy_sendfile = Gauge(
            "mtpu_zerocopy_sendfile_total",
            "Responses shipped by kernel sendfile")
        self.zerocopy_sendfile_bytes = Gauge(
            "mtpu_zerocopy_sendfile_bytes_total",
            "Body bytes shipped by kernel sendfile")
        self.zerocopy_vectored_writes = Gauge(
            "mtpu_zerocopy_vectored_writes_total",
            "Shard appends written as single pwritev batches")
        self.zerocopy_vectored_write_bytes = Gauge(
            "mtpu_zerocopy_vectored_write_bytes_total",
            "Shard bytes written through vectored batches")
        self.zerocopy_fallbacks = Gauge(
            "mtpu_zerocopy_fallbacks_total",
            "Eligible responses that fell back to the buffered writer")
        self.body_pulls = Gauge(
            "mtpu_body_pulls_total",
            "Request-body pulls (one fill of the caller's view from "
            "the connection) by how they ran: native (plain TCP + "
            "Content-Length: one GIL-released poll+recv loop a pull) "
            "or buffered (TLS, chunked transfer encoding, no native "
            "library: through rfile)", ("path",))
        self.body_pull_recvs = Gauge(
            "mtpu_body_pull_recvs_total",
            "recvs those pulls made; over mtpu_body_pulls_total: "
            "recvs a pull", ("path",))
        # Small-object metadata plane (ops/metalanes.py; cf. the
        # reference's format-v2 inline discipline,
        # cmd/xl-storage-format-v2.go).  Synced from DATA_PATH.
        self.meta_publishes = Gauge(
            "mtpu_meta_publishes_total",
            "xl.meta publishes across all drives (solo + batched)")
        self.meta_fsyncs = Gauge(
            "mtpu_meta_fsyncs_total",
            "fsyncs paying for metadata publishes (group commit "
            "amortizes one journal fsync over a whole batch)")
        self.meta_fsyncs_per_object = Gauge(
            "mtpu_meta_fsyncs_per_object",
            "Amortized fsyncs per xl.meta publish (oracle: 1.0)")
        self.meta_group_commits = Gauge(
            "mtpu_meta_group_commits_total",
            "Group-committed metadata batches (one journal fsync each)")
        self.meta_group_items = Gauge(
            "mtpu_meta_group_items_total",
            "xl.meta publishes carried inside group commits")
        self.meta_batch_occupancy = Gauge(
            "mtpu_meta_batch_occupancy",
            "Mean publishes per group commit")
        self.meta_journal_replays = Gauge(
            "mtpu_meta_journal_replays_total",
            "xl.meta entries republished from metadata journal "
            "segments at boot recovery")
        self.meta_read_requests = Gauge(
            "mtpu_meta_read_requests_total",
            "Engine metadata reads (quorum _read_metadata calls)")
        self.meta_read_fanouts = Gauge(
            "mtpu_meta_read_fanouts_total",
            "Metadata read fan-outs by how they ran: inline (on the "
            "request's thread, every drive in-process) or pool "
            "(remote drives); sums to mtpu_meta_read_requests_total",
            ("path",))
        self.meta_lane_dispatches = Gauge(
            "mtpu_meta_lane_dispatches_total",
            "Metadata lane dispatcher rounds")
        self.meta_inline_ops = Gauge(
            "mtpu_meta_inline_ops_total",
            "Lane submits executed inline on the caller's thread "
            "(idle fast path)")
        self.bpool_gets = Gauge(
            "mtpu_bpool_gets_total",
            "Scratch-buffer leases handed out by the aligned pool")
        self.bpool_fallbacks = Gauge(
            "mtpu_bpool_fallbacks_total",
            "Leases served by anonymous mmap (pool off or full)")
        self.bpool_released = Gauge(
            "mtpu_bpool_released_total",
            "Leases explicitly released back to the pool")
        self.bpool_leak_reclaims = Gauge(
            "mtpu_bpool_leak_reclaims_total",
            "Leaked leases reclaimed by the finalize backstop")
        self.bpool_bytes = Gauge(
            "mtpu_bpool_total_bytes", "Aligned-pool arena size")
        self.bpool_in_use = Gauge(
            "mtpu_bpool_in_use_bytes", "Aligned-pool bytes leased out")
        # Device-resident shard plane (ops/devcache.py) + host->device
        # boundary ledger: the instrumented proof that object bytes
        # cross the boundary at most once (first touch ~1.0 byte crossed
        # per byte served, ~0 on cache hits).
        self.devcache_hits = Gauge(
            "mtpu_devcache_hits_total",
            "Reads served from the device-resident shard cache")
        self.devcache_misses = Gauge(
            "mtpu_devcache_misses_total",
            "Shard-cache probes that fell through to disk")
        self.devcache_ratio = Gauge(
            "mtpu_devcache_hit_ratio",
            "Lifetime shard-cache hit ratio")
        self.devcache_fills = Gauge(
            "mtpu_devcache_fills_total",
            "Verified fast-path reads admitted to the shard cache")
        self.devcache_evictions = Gauge(
            "mtpu_devcache_evictions_total",
            "Shard-cache entries evicted by the LRU capacity bound")
        self.devcache_invalidations = Gauge(
            "mtpu_devcache_invalidations_total",
            "Bucket mutations noted by the shard cache (_mark_dirty)")
        self.devcache_stale_drops = Gauge(
            "mtpu_devcache_stale_drops_total",
            "Entries/fills dropped by generation mismatch")
        self.devcache_rejects = Gauge(
            "mtpu_devcache_rejects_total",
            "Fills rejected (range larger than the cache capacity)")
        self.devcache_entries = Gauge(
            "mtpu_devcache_entries",
            "Resident shard-cache entries")
        self.devcache_resident = Gauge(
            "mtpu_devcache_resident_bytes",
            "Payload bytes resident in the shard cache")
        self.devcache_capacity = Gauge(
            "mtpu_devcache_capacity_bytes",
            "Shard-cache capacity bound (MTPU_DEVCACHE_MB)")
        self.h2d_bytes = Gauge(
            "mtpu_h2d_bytes_total",
            "Bytes that crossed the host->device boundary")
        self.d2h_bytes = Gauge(
            "mtpu_d2h_bytes_total",
            "Bytes of results the dispatch kernels brought back from "
            "the device, pad rows included")
        self.d2h_fetches = Gauge(
            "mtpu_d2h_fetches_total",
            "Device arrays the dispatch kernels brought back")
        self.d2h_early_starts = Gauge(
            "mtpu_d2h_early_starts_total",
            "Device arrays whose way back was begun at their launch")
        self.lane_result_copy_bytes = Gauge(
            "mtpu_lane_result_copy_bytes_total",
            "Bytes of dispatch results a kernel's resolve copied on the "
            "host after the fetch (0: every result a view of an array "
            "the runtime filled)")
        self.h2d_dispatches = Gauge(
            "mtpu_h2d_dispatches_total",
            "Host->device upload crossings (device_put calls)")
        self.h2d_lane_bytes = Gauge(
            "mtpu_h2d_lane_bytes_total",
            "Host->device bytes per device lane")
        self.h2d_lane_dispatches = Gauge(
            "mtpu_h2d_lane_dispatches_total",
            "Host->device crossings per device lane")
        self.h2d_pipeline_dispatches = Gauge(
            "mtpu_h2d_pipeline_dispatches_total",
            "Coalesced batches shipped through the pinned-staging "
            "double-buffered upload pipeline")
        self.h2d_overlap_seconds = Gauge(
            "mtpu_h2d_overlap_seconds_total",
            "Host pack/upload time overlapped with device execution")
        self.h2d_pack_seconds = Gauge(
            "mtpu_h2d_pack_seconds_total",
            "Time packing batches into pinned staging buffers")
        self.h2d_upload_seconds = Gauge(
            "mtpu_h2d_upload_seconds_total",
            "Time issuing async device_put uploads from staging")
        self.h2d_resolve_seconds = Gauge(
            "mtpu_h2d_resolve_seconds_total",
            "Time syncing pipelined kernel results (resolve phase)")
        # ILM transition/restore + warm-tier families (bucket/tier.py;
        # cf. getClusterTierMetrics, cmd/metrics-v3-cluster-usage.go).
        self.ilm_transitioned = Gauge(
            "mtpu_ilm_transitioned_total",
            "Versions moved to a warm tier (stub left hot)")
        self.ilm_transition_bytes = Gauge(
            "mtpu_ilm_transition_bytes_total",
            "Bytes streamed to warm tiers by transitions")
        self.ilm_transition_errors = Gauge(
            "mtpu_ilm_transition_errors_total",
            "Transitions aborted by tier faults (journal reaps)")
        self.ilm_restored = Gauge(
            "mtpu_ilm_restored_total",
            "Restore-on-POST rehydrations completed")
        self.ilm_restore_bytes = Gauge(
            "mtpu_ilm_restore_bytes_total",
            "Bytes streamed back hot by restores")
        self.ilm_restore_expired = Gauge(
            "mtpu_ilm_restore_expired_total",
            "Temporary restores re-expired by the scanner")
        self.ilm_journal_pending = Gauge(
            "mtpu_ilm_journal_pending",
            "Tier-journal records awaiting resolution (drains to 0)")
        self.ilm_journal_replayed = Gauge(
            "mtpu_ilm_journal_replayed_total",
            "Journal records resolved by boot replay")
        self.ilm_orphans_reaped = Gauge(
            "mtpu_ilm_orphans_reaped_total",
            "Orphaned tier objects reaped via the journal")
        # Bucket replication families (bucket/replication.py; cf.
        # getReplicationSiteMetrics, cmd/metrics-v2.go replication).
        self.repl_queued = Gauge(
            "mtpu_repl_queued",
            "Replication tasks in backlog or in flight (drains to 0)")
        self.repl_completed = Gauge(
            "mtpu_repl_completed_total",
            "Replication tasks copied to their target")
        self.repl_failed = Gauge(
            "mtpu_repl_failed_total",
            "Replication tasks whose FIRST attempt failed")
        self.repl_retries = Gauge(
            "mtpu_repl_retries_total",
            "Replication re-attempts after a failed first try")
        self.repl_dropped = Gauge(
            "mtpu_repl_dropped_total",
            "Journaled tasks dropped (bucket unwired / source gone)")
        self.repl_bytes = Gauge(
            "mtpu_repl_bytes_total",
            "Bytes copied to replication targets")
        self.repl_proxied = Gauge(
            "mtpu_repl_proxied_reads_total",
            "GETs served by proxying to a replication target")
        self.repl_journal_pending = Gauge(
            "mtpu_repl_journal_pending",
            "Intent-journal records awaiting completion (drains to 0)")
        self.repl_journal_replayed = Gauge(
            "mtpu_repl_journal_replayed_total",
            "Intents restored into the backlog by boot replay")
        self.repl_lag = Gauge(
            "mtpu_repl_lag_seconds",
            "Age of the oldest unreplicated task per target bucket",
            ("target",))
        self.repl_breaker_open = Gauge(
            "mtpu_repl_breaker_open",
            "Per-target breakers currently open (target unreachable)")
        self.tier_objects = Gauge(
            "mtpu_tier_objects",
            "Objects currently resident in the warm tier", ("tier",))
        self.tier_bytes = Gauge(
            "mtpu_tier_bytes",
            "Bytes currently resident in the warm tier", ("tier",))
        self.tier_read_through = Gauge(
            "mtpu_tier_read_through_total",
            "Stub GET/HEAD reads streamed through from tiers")
        self.tier_freed = Gauge(
            "mtpu_tier_freed_total",
            "Tier objects deleted through the journal")
        # Multi-pool placement + decommission families (cf.
        # getClusterHealthMetrics pool rows, cmd/metrics-v3-cluster.go).
        self.pool_total_bytes = Gauge(
            "mtpu_pool_total_bytes", "Pool raw capacity", ("pool",))
        self.pool_free_bytes = Gauge(
            "mtpu_pool_free_bytes", "Pool free capacity", ("pool",))
        self.pool_draining = Gauge(
            "mtpu_pool_draining",
            "Pool is excluded from new placement (decommission)",
            ("pool",))
        self.decom_state = Gauge(
            "mtpu_decom_state",
            "Decommission state: 0 draining, 1 paused, 2 complete, "
            "3 cancelled, 4 failed", ("pool",))
        self.decom_objects_moved = Gauge(
            "mtpu_decom_objects_moved_total",
            "Objects fully drained off the pool", ("pool",))
        self.decom_objects_remaining = Gauge(
            "mtpu_decom_objects_remaining",
            "Objects still to drain", ("pool",))
        self.decom_versions_moved = Gauge(
            "mtpu_decom_versions_moved_total",
            "Versions re-PUT off the pool", ("pool",))
        self.decom_bytes_moved = Gauge(
            "mtpu_decom_bytes_moved_total",
            "Bytes re-PUT off the pool", ("pool",))
        self.decom_bytes_per_sec = Gauge(
            "mtpu_decom_bytes_per_sec",
            "Current drain throughput", ("pool",))
        self.decom_uploads_relocated = Gauge(
            "mtpu_decom_uploads_relocated_total",
            "Pending multipart uploads re-staged off the pool",
            ("pool",))
        # Sliding last-minute SLO families (observe/lastminute.py):
        # merged from the per-worker ring at scrape time.
        self.api_lm_count = Gauge(
            "mtpu_api_last_minute_count",
            "Requests in the sliding SLO window by API", ("api",))
        self.api_lm_errors = Gauge(
            "mtpu_api_last_minute_errors",
            "Error responses in the sliding SLO window by API",
            ("api",))
        self.api_lm_p50 = Gauge(
            "mtpu_api_last_minute_p50",
            "Sliding-window p50 latency in ms by API", ("api",))
        self.api_lm_p99 = Gauge(
            "mtpu_api_last_minute_p99",
            "Sliding-window p99 latency in ms by API", ("api",))
        self.api_lm_sheds = Gauge(
            "mtpu_api_last_minute_sheds",
            "Admission-shed 503s in the sliding SLO window by API "
            "(distinct from errors: a shed is deliberate overload "
            "protection, not a server fault)", ("api",))
        # Audit-plane delivery families (observe/audit.py): per-target
        # delivered/shed/retried entry counts.
        self.audit_emitted = Gauge(
            "mtpu_audit_emitted_total",
            "Audit entries delivered to the sink", ("target",))
        self.audit_dropped = Gauge(
            "mtpu_audit_dropped_total",
            "Audit entries shed (bounded queue full or sink dead "
            "after retries)", ("target",))
        self.audit_retries = Gauge(
            "mtpu_audit_retries_total",
            "Audit delivery re-attempts (webhook backoff)", ("target",))
        # Overload-plane families (server/qos.py): admission slots,
        # deadline queue, tenant/bucket throttles, background yield —
        # synced from the fork-shared slab at scrape time.
        self.qos_inflight = Gauge(
            "mtpu_qos_requests_inflight",
            "Admission slots currently held (pool-wide: the slab is "
            "fork-shared)")
        self.qos_queue_depth = Gauge(
            "mtpu_qos_queue_depth",
            "Requests waiting in the admission deadline queue")
        self.qos_pressure = Gauge(
            "mtpu_qos_pressure",
            "Admission occupancy EMA in [0,1] — the signal background "
            "planes yield to")
        self.qos_admitted = Gauge(
            "mtpu_qos_admitted_total",
            "Requests admitted through the overload plane by tenant "
            "class", ("tenant_class",))
        self.qos_shed = Gauge(
            "mtpu_qos_shed_total",
            "Requests shed with 503 SlowDown by tenant class",
            ("tenant_class",))
        self.qos_shed_reason = Gauge(
            "mtpu_qos_shed_reason_total",
            "Admission sheds by cause (queue: bounded queue full; "
            "deadline: MTPU_REQUESTS_DEADLINE_MS expired waiting)",
            ("reason",))
        self.qos_queue_wait = Gauge(
            "mtpu_qos_queue_wait_seconds_total",
            "Summed admission-queue wait of requests that were "
            "eventually admitted")
        self.qos_tenant_throttled = Gauge(
            "mtpu_qos_tenant_throttled_total",
            "Requests refused by per-tenant token buckets (req/s or "
            "bandwidth)")
        self.qos_bucket_throttled = Gauge(
            "mtpu_qos_bucket_throttled_total",
            "Requests refused by per-bucket bandwidth budgets")
        self.qos_bg_yields = Gauge(
            "mtpu_qos_bg_yields_total",
            "Background-plane yields to foreground pressure (shrunk "
            "batch concurrency + paced batches)", ("plane",))
        self.bandwidth = BandwidthMonitor()
        self.last_minute = ApiWindow()

    def observe_api(self, api: str, duration_s: float,
                    error: bool = False, nbytes: int = 0,
                    shed: bool = False) -> None:
        """Feed the sliding SLO window — lock-free, called once per
        request with the span-style API name (api.PutObject, ...).
        `shed` marks an admission-control 503 as its own class: shed
        ≠ server error in the SLO window (deliberate overload
        protection must not page anyone about error budgets)."""
        self.last_minute.observe(api, duration_s, error, nbytes,
                                 shed=shed)

    def update_qos(self, plane) -> None:
        """Refresh overload-plane gauges from the fork-shared slab
        (scrape time, same pattern as update_audit)."""
        if plane is None:
            return
        st = plane.stats()
        self.qos_inflight.set(st["inflight"])
        self.qos_queue_depth.set(st["waiting"])
        self.qos_pressure.set(st["pressure"])
        self.qos_queue_wait.set(st["queue_wait_seconds"])
        self.qos_tenant_throttled.set(st["tenant_throttled"])
        self.qos_bucket_throttled.set(st["bucket_throttled"])
        self.qos_shed_reason.set(st["shed_queue"], reason="queue")
        self.qos_shed_reason.set(st["shed_deadline"], reason="deadline")
        for klass, row in st["classes"].items():
            self.qos_admitted.set(row["admitted"], tenant_class=klass)
            self.qos_shed.set(row["shed"], tenant_class=klass)
        self.qos_bg_yields.set(st["bg_yields"], plane="all")
        for name, n in st["bg_yields_by_plane"].items():
            self.qos_bg_yields.set(n, plane=name)

    def update_audit(self, targets) -> None:
        """Refresh per-target audit delivery gauges (scrape time)."""
        for t in targets:
            s = t.stats() if hasattr(t, "stats") else None
            if s is None:
                continue
            name = s["target"]
            self.audit_emitted.set(s["emitted"], target=name)
            self.audit_dropped.set(s["dropped"], target=name)
            self.audit_retries.set(s["retries"], target=name)

    def observe_request(self, api: str, status: int, duration_s: float,
                        rx: int, tx: int, bucket: str = "") -> None:
        self.api_requests.inc(api=api, status=str(status))
        if status >= 400:
            self.api_errors.inc(code=str(status))
        self.latency.observe(duration_s)
        self.bytes_rx.inc(rx)
        self.bytes_tx.inc(tx)
        if bucket:
            self.bandwidth.record(bucket, rx, tx)

    def update_ilm(self, tier_mgr) -> None:
        """Refresh ILM/tier gauges from TierManager.stats() (scrape
        time, same pattern as the hot-cache block)."""
        if tier_mgr is None:
            return
        st = tier_mgr.stats()
        self.ilm_transitioned.set(st["transitioned"])
        self.ilm_transition_bytes.set(st["transition_bytes"])
        self.ilm_transition_errors.set(st["transition_errors"])
        self.ilm_restored.set(st["restored"])
        self.ilm_restore_bytes.set(st["restore_bytes"])
        self.ilm_restore_expired.set(st["restore_expired"])
        self.ilm_journal_pending.set(st["journal_pending"])
        self.ilm_journal_replayed.set(st["replayed"])
        self.ilm_orphans_reaped.set(st["orphans_reaped"])
        self.tier_read_through.set(st["read_through"])
        self.tier_freed.set(st["freed"])
        for tname, usage in st["tiers"].items():
            self.tier_objects.set(usage["objects"], tier=tname)
            self.tier_bytes.set(usage["bytes"], tier=tname)

    def update_replication(self, repl) -> None:
        """Refresh replication gauges from ReplicationPool.stats()
        (scrape time; the legacy oracle reports its smaller dict and
        the journal-only gauges stay 0)."""
        if repl is None:
            return
        st = repl.stats()
        self.repl_queued.set(st.get("queued", 0))
        self.repl_completed.set(st.get("completed", 0))
        self.repl_failed.set(st.get("failed", 0))
        self.repl_retries.set(st.get("retries", 0))
        self.repl_dropped.set(st.get("dropped", 0))
        self.repl_bytes.set(st.get("bytesReplicated", 0))
        self.repl_proxied.set(st.get("proxiedReads", 0))
        self.repl_journal_pending.set(st.get("journalPending", 0))
        self.repl_journal_replayed.set(st.get("replayed", 0))
        lag = st.get("lagSeconds") or {}
        # a drained target's lag pins to 0 (stale label values would
        # otherwise report the last backlog age forever)
        for tb in getattr(self, "_repl_lag_seen", set()) | set(lag):
            self.repl_lag.set(lag.get(tb, 0.0), target=tb)
        self._repl_lag_seen = set(lag) | getattr(
            self, "_repl_lag_seen", set())
        self.repl_breaker_open.set(len(st.get("breakersOpen") or {}))

    def update_cluster(self, pools, scanner=None, tier_mgr=None) -> None:
        self.update_ilm(tier_mgr)
        cm = getattr(pools, "cache_metrics", None)
        if callable(cm):
            c = cm()
            self.cache_hits.set(c["hits"])
            self.cache_misses.set(c["misses"])
            self.cache_evictions.set(c["evictions"])
            self.cache_usage.set(c["usage_bytes"])
            self.cache_max.set(c["max_bytes"])
        tier = getattr(pools, "hot_tier", None)
        if tier is not None:
            hs = tier.stats()
            self.hotcache_hits.set(hs["hits"])
            self.hotcache_misses.set(hs["misses"])
            self.hotcache_meta_hits.set(hs["meta_hits"])
            self.hotcache_ratio.set(round(hs["hit_ratio"], 6))
            self.hotcache_fills.set(hs["fills"])
            self.hotcache_evictions.set(hs["evictions"])
            self.hotcache_bypassed.set(hs["bypassed"])
            self.hotcache_stale.set(hs["stale_gen"])
            self.hotcache_invalidations.set(hs["invalidations"])
            self.hotcache_entries.set(hs["entries"])
            self.hotcache_bytes.set(hs["cached_bytes"])
            self.hotcache_segment.set(hs["segment_bytes"])
        online = offline = 0
        mrf_pending = mrf_healed = mrf_dropped = mrf_retries = 0
        mrf_seen: set[int] = set()
        _STATE = {"ok": 0, "suspect": 1, "offline": 2}
        for pi, pool in enumerate(pools.pools):
            for si, es in enumerate(getattr(pool, "sets", [pool])):
                for di, d in enumerate(es.drives):
                    state = 2
                    if d is None:
                        offline += 1
                    elif hasattr(d, "is_online") and not d.is_online():
                        offline += 1
                    elif hasattr(d, "health_state") \
                            and d.health_state() == "offline":
                        # Breaker-open circuit: physically present but
                        # out of the data path.
                        offline += 1
                    else:
                        online += 1
                        if hasattr(d, "health_state"):
                            state = _STATE.get(d.health_state(), 0)
                        else:
                            state = 0
                    self.drive_state.set(state, pool=str(pi),
                                         set=str(si), drive=str(di))
                mrf = getattr(es, "mrf", None)
                if mrf is not None and id(mrf) not in mrf_seen:
                    # One queue may serve every set of a pool — count
                    # it once.
                    mrf_seen.add(id(mrf))
                    mrf_pending += mrf.pending()
                    mrf_healed += mrf.healed
                    mrf_dropped += mrf.dropped
                    mrf_retries += getattr(mrf, "retries", 0)
        self.drive_online.set(online)
        self.drive_offline.set(offline)
        if hasattr(pools, "pool_status"):
            _DSTATE = {"draining": 0, "paused": 1, "complete": 2,
                       "cancelled": 3, "failed": 4}
            for row in pools.pool_status():
                pl = str(row["pool"])
                self.pool_total_bytes.set(row["total"], pool=pl)
                self.pool_free_bytes.set(row["free"], pool=pl)
                self.pool_draining.set(int(row["draining"]), pool=pl)
                ds = row.get("decommission")
                if ds:
                    self.decom_state.set(
                        _DSTATE.get(ds["state"], 4), pool=pl)
                    self.decom_objects_moved.set(
                        ds["objects_moved"], pool=pl)
                    self.decom_objects_remaining.set(
                        ds["objects_remaining"], pool=pl)
                    self.decom_versions_moved.set(
                        ds["versions_moved"], pool=pl)
                    self.decom_bytes_moved.set(
                        ds["bytes_moved"], pool=pl)
                    self.decom_bytes_per_sec.set(
                        ds["bytes_per_sec"], pool=pl)
                    self.decom_uploads_relocated.set(
                        ds["uploads_relocated"], pool=pl)
        self.mrf_pending.set(mrf_pending)
        self.mrf_healed.set(mrf_healed)
        self.mrf_dropped.set(mrf_dropped)
        self.mrf_retries.set(mrf_retries)
        if scanner is not None:
            usage = scanner.latest_usage()
            if usage is not None:
                for bucket, u in usage.buckets.items():
                    self.bucket_usage.set(u.bytes, bucket=bucket)
                    self.bucket_objects.set(u.objects, bucket=bucket)

    def update_peers(self, clients) -> None:
        """Refresh per-endpoint peer gauges from RPCClient liveness
        (called on scrape with the cluster node's peer clients)."""
        for cli in clients:
            info = cli.peer_info()
            ep = info["endpoint"]
            self.peer_state.set(1 if info["online"] else 0, endpoint=ep)
            self.peer_transitions.set(info["transitions"], endpoint=ep)
            self.peer_last_seen.set(info["last_seen_ago_s"], endpoint=ep)
            self.peer_rpc_timeout.set(info["timeout_s"], endpoint=ep)

    def _sync_datapath(self) -> None:
        snap = DATA_PATH.snapshot()
        self.heal_bytes.set(snap["heal_bytes"])
        self.heal_source_bytes.set(snap["heal_source_bytes"])
        for stage, s in snap["heal_stage_s"].items():
            self.heal_stage_seconds.set(s, stage=stage)
        self.heal_batches.set(snap["heal_batches"])
        self.heal_batch_occupancy.set(snap["heal_batch_occupancy"])
        self.degraded_reads.set(snap["degraded_reads"])
        self.degraded_bytes.set(snap["degraded_bytes"])
        self.degraded_seconds.set(snap["degraded_seconds"])
        self.healthy_reads.set(snap["healthy_reads"])
        self.healthy_bytes.set(snap["healthy_bytes"])
        for stage, s in snap["healthy_stage_s"].items():
            self.healthy_stage_seconds.set(s, stage=stage)
        self.fastpath_fallbacks.set(snap["fastpath_fallbacks"])
        self.mp_batches.set(snap["mp_batches"])
        self.mp_bytes.set(snap["mp_bytes"])
        for stage, s in snap["mp_stage_s"].items():
            self.mp_stage_seconds.set(s, stage=stage)
        self.co_dispatches.set(snap["co_dispatches"])
        self.co_items.set(snap["co_items"])
        self.co_blocks.set(snap["co_weight"])
        self.co_occupancy.set(snap["co_occupancy"])
        self.co_wait_seconds.set(snap["co_wait_s"])
        self.co_batch_faults.set(snap["co_batch_faults"])
        self.co_member_retries.set(snap["co_member_retries"])
        self.co_fallbacks.set(snap["co_fallbacks"])
        for dev, row in snap["lanes"].items():
            self.device_lane_dispatches.set(row["dispatches"],
                                            device=str(dev))
            self.device_lane_occupancy.set(
                row["items"] / row["dispatches"]
                if row["dispatches"] else 0.0, device=str(dev))
            self.device_lane_queue_wait.set(row["wait_s"],
                                            device=str(dev))
            self.device_lane_rows.set(row["rows"], lane=str(dev))
            self.device_lane_padded_rows.set(row["padded_rows"],
                                             lane=str(dev))
        self.jit_compiles.set(snap["jit_compiles"])
        self.jit_compile_seconds.set(snap["jit_compile_s"])
        for plane, blocks in snap["encode_blocks"].items():
            self.encode_blocks.set(blocks, plane=plane)
        self.verify_blocks.set(snap["verify_blocks"])
        self.decode_blocks.set(snap["decode_blocks"])
        self.decode_patterns.set(snap["decode_patterns"])
        self.stage_pad_bytes.set(snap["stage_pad_bytes"])
        self.put_fresh_buffer_bytes.set(snap["put_fresh_buffer_bytes"])
        for site, n in snap["get_fresh_buffer_bytes"].items():
            self.get_fresh_buffer_bytes.set(n, site=site)
        for site, n in snap["get_leased_buffer_bytes"].items():
            self.get_leased_buffer_bytes.set(n, site=site)
        for path, n in snap["shard_rows_read"].items():
            self.shard_rows_read.set(n, path=path)
        for site, n in snap["host_hash_bytes"].items():
            self.host_hash_bytes.set(n, site=site)
        from ..engine import segarena as _segarena
        self.get_arena_free_bytes.set(_segarena.POOL.free_bytes())
        self.request_stall_episodes.set(snap["request_stall_episodes"])
        self.ipc_submits.set(snap["ipc_submits"])
        self.ipc_results.set(snap["ipc_results"])
        self.ipc_fallbacks.set(snap["ipc_fallbacks"])
        self.ipc_owner_deaths.set(snap["ipc_owner_deaths"])
        self.hedged_reads.set(snap["hedged_reads"])
        self.hedge_fired.set(snap["hedge_fired"])
        self.hedge_spares.set(snap["hedge_spares"])
        self.hedge_wins.set(snap["hedge_wins"])
        for state, n in snap["drive_transitions"].items():
            self.drive_transitions.set(n, state=state)
        self.dg_md5_calls.set(snap["dg_md5_calls"])
        self.dg_md5_streams.set(snap["dg_md5_streams"])
        self.dg_md5_bytes.set(snap["dg_md5_bytes"])
        self.dg_md5_occupancy.set(snap["dg_md5_occupancy"])
        self.dg_sha_calls.set(snap["dg_sha_calls"])
        self.dg_sha_bufs.set(snap["dg_sha_bufs"])
        self.dg_sha_bytes.set(snap["dg_sha_bytes"])
        self.recovery_sweeps.set(snap["recovery_sweeps"])
        self.recovery_tmp.set(snap["recovery_tmp_entries"])
        self.recovery_mp_stage.set(snap["recovery_mp_stage"])
        self.mrf_replayed.set(snap["mrf_replayed"])
        self.drains.set(snap["drains"])
        self.drain_leftover.set(snap["drain_leftover"])
        self.drain_seconds.set(snap["drain_seconds"])
        for state, n in snap["peer_transitions"].items():
            self.peer_flaps.set(n, state=state)
        self.rpc_retries.set(snap["rpc_retries"])
        self.rpc_deadline_exceeded.set(snap["rpc_deadline_exceeded"])
        for kind, n in snap["netchaos_injected"].items():
            self.netchaos_injected.set(n, kind=kind)
        self.zerocopy_hot_views.set(snap["zerocopy_hot_views"])
        self.zerocopy_hot_view_bytes.set(snap["zerocopy_hot_view_bytes"])
        self.zerocopy_sendmsg.set(snap["zerocopy_sendmsg"])
        self.zerocopy_sendmsg_bytes.set(snap["zerocopy_sendmsg_bytes"])
        self.zerocopy_sendfile.set(snap["zerocopy_sendfile"])
        self.zerocopy_sendfile_bytes.set(snap["zerocopy_sendfile_bytes"])
        self.zerocopy_vectored_writes.set(snap["zerocopy_vectored_writes"])
        self.zerocopy_vectored_write_bytes.set(
            snap["zerocopy_vectored_write_bytes"])
        self.zerocopy_fallbacks.set(snap["zerocopy_fallbacks"])
        for path, n in snap["body_pulls"].items():
            self.body_pulls.set(n, path=path)
            self.body_pull_recvs.set(snap["body_pull_recvs"][path],
                                     path=path)
        self.meta_publishes.set(snap["meta_publishes"])
        self.meta_fsyncs.set(snap["meta_fsyncs"])
        self.meta_fsyncs_per_object.set(
            round(snap["meta_fsyncs_per_object"], 6))
        self.meta_group_commits.set(snap["meta_group_commits"])
        self.meta_group_items.set(snap["meta_group_items"])
        self.meta_batch_occupancy.set(
            round(snap["meta_batch_occupancy"], 6))
        self.meta_journal_replays.set(snap["meta_journal_replays"])
        self.meta_read_requests.set(snap["meta_read_requests"])
        for path, n in snap["meta_read_fanouts"].items():
            self.meta_read_fanouts.set(n, path=path)
        self.meta_lane_dispatches.set(snap["meta_lane_dispatches"])
        self.meta_inline_ops.set(snap["meta_inline_ops"])
        # Aligned-buffer pool: scrape-only, never forces the shared
        # segment into existence (bpool.stats() is None until first use).
        from ..ops import bpool as _bpool
        bsnap = _bpool.stats()
        if bsnap is not None:
            self.bpool_gets.set(bsnap["gets"])
            self.bpool_fallbacks.set(bsnap["fallbacks"])
            self.bpool_released.set(bsnap["released"])
            self.bpool_leak_reclaims.set(bsnap["leak_reclaims"])
            self.bpool_bytes.set(bsnap["pool_bytes"])
            self.bpool_in_use.set(bsnap["in_use_bytes"])
        # Device-resident shard cache + H2D boundary ledger: scrape-only
        # pulls, same pattern as bpool (None until first use).
        from ..ops import devcache as _devcache
        dsnap = _devcache.stats()
        if dsnap is not None:
            self.devcache_hits.set(dsnap["hits"])
            self.devcache_misses.set(dsnap["misses"])
            self.devcache_ratio.set(round(dsnap["hit_ratio"], 6))
            self.devcache_fills.set(dsnap["fills"])
            self.devcache_evictions.set(dsnap["evictions"])
            self.devcache_invalidations.set(dsnap["invalidations"])
            self.devcache_stale_drops.set(dsnap["stale_drops"])
            self.devcache_rejects.set(dsnap["rejects"])
            self.devcache_entries.set(dsnap["entries"])
            self.devcache_resident.set(dsnap["resident_bytes"])
            self.devcache_capacity.set(dsnap["capacity_bytes"])
        hsnap = _devcache.h2d_stats()
        self.h2d_bytes.set(hsnap["h2d_bytes"])
        self.d2h_bytes.set(hsnap["d2h_bytes"])
        self.d2h_fetches.set(hsnap["d2h_fetches"])
        self.d2h_early_starts.set(hsnap["d2h_early_starts"])
        self.lane_result_copy_bytes.set(hsnap["result_copy_bytes"])
        self.h2d_dispatches.set(hsnap["h2d_dispatches"])
        for dev, row in hsnap["lanes"].items():
            self.h2d_lane_bytes.set(row["h2d_bytes"], device=str(dev))
            self.h2d_lane_dispatches.set(row["h2d_dispatches"],
                                         device=str(dev))
        from ..ops import coalesce as _coalesce
        from ..ops import devices as _devices
        co = _coalesce._CO
        lanes = {}
        if co is not None:
            cst = co.stats()
            lanes = cst["lanes"]
            self.h2d_pipeline_dispatches.set(cst["pipeline_dispatches"])
            self.h2d_overlap_seconds.set(cst["overlap_s"])
            self.h2d_pack_seconds.set(cst["pack_s"])
            self.h2d_upload_seconds.set(cst["h2d_s"])
            self.h2d_resolve_seconds.set(cst["resolve_s"])
        # Every state of every lane from boot, at 0 until the lane is
        # made: a window in which nothing was counted reads 0, not
        # missing.  The lane count is asked for only where the process
        # already knows its devices (a scrape never starts JAX).
        nlanes = _devices.n_devices() if _devices._VISIBLE else 1
        for d in range(max(nlanes, len(lanes))):
            state_s = lanes.get(d, {}).get("state_s", {})
            for state in _coalesce.DispatchLane.STATES:
                self.device_lane_state_seconds.set(
                    state_s.get(state, 0.0), lane=str(d), state=state)

    def _sync_spans(self) -> None:
        # Imported lazily: span.py is the one observe module allowed to
        # stay import-light (it sits on every request's hot path).
        from .span import BUCKETS_MS, TRACER, layer_of, root_self_stage
        snap = TRACER.snapshot()

        def set_self(agg: dict, api: str, stage: str) -> None:
            # A stage's self time and its two parts: ran, waited.
            labels = {"api": api, "stage": stage,
                      "layer": layer_of(stage)}
            self.trace_stage_self_ms.set(agg["self_ms"], **labels)
            self.trace_stage_self_cpu_ms.set(agg["self_cpu_ms"],
                                             **labels)
            self.trace_stage_self_wait_ms.set(agg["self_wait_ms"],
                                              **labels)

        for api, a in snap["apis"].items():
            self.trace_api_count.set(a["count"], api=api)
            self.trace_api_errors.set(a["errors"], api=api)
            # A request root's own self time is the front door's; a
            # lane dispatch's root is its own stage.
            set_self(a, api, root_self_stage(api))
            for q in ("p50", "p90", "p99"):
                self.trace_api_latency.set(a[f"{q}_ms"], api=api,
                                           quantile=q)
            for stage, st in a["stages"].items():
                self.trace_stage_count.set(st["count"], api=api,
                                           stage=stage)
                self.trace_stage_ms.set(st["total_ms"], api=api,
                                        stage=stage)
                set_self(st, api, stage)
                cum = 0
                for i, bound in enumerate(BUCKETS_MS):
                    cum += st["buckets"][i]
                    le = ("+Inf" if bound == float("inf")
                          else f"{bound:g}")
                    self.trace_stage_hist.set(cum, api=api, stage=stage,
                                              le=le)

    def _sync_last_minute(self) -> None:
        for api, row in self.last_minute.snapshot().items():
            self.api_lm_count.set(row["count"], api=api)
            self.api_lm_errors.set(row["errors"], api=api)
            self.api_lm_sheds.set(row["sheds"], api=api)
            self.api_lm_p50.set(row["p50_ms"], api=api)
            self.api_lm_p99.set(row["p99_ms"], api=api)

    def families(self) -> list:
        """Every exported metric family, in definition order — the
        enumerable registry the render loop and the boot self-test
        (ops/selftest.metrics_registry_self_test) both walk, so a
        family can never exist without being rendered and checked."""
        return [m for m in self.__dict__.values()
                if isinstance(m, (Counter, Histogram))]

    def render(self) -> str:
        self._sync_datapath()
        self._sync_spans()
        self._sync_last_minute()
        out: list[str] = []
        for m in self.families():
            m.render(out)
        return "\n".join(out) + "\n"


def label_sample(line: str, key: str, value: str) -> str:
    """Inject one label into a Prometheus sample line
    (`name{a="b"} v` or `name v`)."""
    head, _, val = line.rpartition(" ")
    if head.endswith("}"):
        return f'{head[:-1]},{key}="{value}"}} {val}'
    return f'{head}{{{key}="{value}"}} {val}'


def merge_prom(sections: list[tuple[str, str]]) -> str:
    """Merge per-node Prometheus renders into one valid exposition:
    HELP/TYPE once per family (first seen wins), every sample line
    relabeled with node="host:port", samples grouped under their
    family.  Input sections are (node, text) pairs as produced by
    S3Server.local_metrics_text on each node."""
    meta: dict[str, list[str | None]] = {}    # family -> [help, type]
    rows: dict[str, list[str]] = {}
    order: list[str] = []
    for node, text in sections:
        current = None
        for line in text.splitlines():
            if not line.strip():
                continue
            if line.startswith(("# HELP ", "# TYPE ")):
                fam = line.split(None, 3)[2]
                if fam not in rows:
                    rows[fam] = []
                    meta[fam] = [None, None]
                    order.append(fam)
                slot = 0 if line.startswith("# HELP ") else 1
                if meta[fam][slot] is None:
                    meta[fam][slot] = line
                current = fam
                continue
            if line.startswith("#"):
                continue
            if current is None:
                # Bare sample with no preceding comment: group under
                # its own metric name.
                current = line.split("{", 1)[0].split()[0]
                if current not in rows:
                    rows[current] = []
                    meta[current] = [None, None]
                    order.append(current)
            rows[current].append(label_sample(line, "node", node))
    out: list[str] = []
    for fam in order:
        for comment in meta[fam]:
            if comment is not None:
                out.append(comment)
        out.extend(rows[fam])
    return "\n".join(out) + "\n"
