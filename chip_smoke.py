#!/usr/bin/env python3
"""chip_smoke.py — does the served path work on the chip?  The quickest proof.

Starts `python -m minio_tpu.server` (the normal entry point) as the ONE
process that touches JAX, with JAX_PLATFORMS=tpu so that a missing chip is an
error and never a quiet CPU run, and drives it as a client would: SigV4 HTTP
PUT / GET / ranged GET / multipart at EC:8+4 over 12 drives, a degraded GET
with two data shards gone, an admin heal.  Every byte that comes back is
compared with the seeded source; parity and bitrot digests on disk are
compared with a numpy reference (ReedSolomonCPU, mxh256) computed here,
independent of the device code; the server's own counters must show that the
bytes crossed to the device and that no fallback or fault path ran.

This script never imports JAX (a chip belongs to one process at a time, and
that process is the server); it learns the device from the `device` block of
admin healthinfo.  Any failed phase ends it non-zero.  Readings it prints are
client-side smoke readings of one run, not benchmark results.

    python chip_smoke.py                 one chip (what the driver runs)
    python chip_smoke.py --chips 4       four-chip host: four EC:2+2 sets, a
                                         set a lane (what the server chooses
                                         itself), then the same requests with
                                         the mesh forced (MTPU_MESH=1) and on
                                         one lane (MTPU_DEVICES=1 MTPU_MESH=0),
                                         compared shard by shard
    JAX_PLATFORMS=cpu python chip_smoke.py --small
                                         rehearsal: every phase at a small
                                         size, then exit 1 (no chip)

Last stdout line on success, and only then:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import concurrent.futures as cf
import hashlib
import io
import json
import os
import re
import shutil
import signal
import socket
import statistics
import subprocess
import sys
import tempfile
import time
import urllib.request

CHECKOUT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, CHECKOUT)

import numpy as np  # noqa: E402

from minio_tpu.ops.erasure_cpu import ReedSolomonCPU  # noqa: E402
from minio_tpu.ops.mxhash import mxh256  # noqa: E402
from minio_tpu.server.client import S3Client  # noqa: E402
from minio_tpu.storage.xlmeta import XLMeta  # noqa: E402

MIB = 1 << 20
BLOCK = MIB                     # the engine's erasure block (blockSizeV2)
HASH = 32                       # bitrot frame = [32-byte digest | shard block]
BUCKET = "smoke"
CLIENTS = 4
FALLBACK_COUNTERS = ("mtpu_coalesce_fallbacks_total",
                     "mtpu_coalesce_batch_faults_total",
                     "mtpu_ipc_dispatch_fallbacks_total")


class SmokeFailure(Exception):
    pass


def need(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def body_for(seed: int, name: str, size: int) -> bytes:
    """The object's bytes, a pure function of (--seed, name, size)."""
    tag = int.from_bytes(hashlib.sha256(name.encode()).digest()[:8], "big")
    return np.random.default_rng([seed, tag]).bytes(size)


# -- the server child ----------------------------------------------------------

class Server:
    """`python -m minio_tpu.server` over `ndrives` fresh directories; the
    only child of this script, and the only process that may touch the
    chip.  Always stopped; a clean stop (SIGTERM -> exit 0) is required
    unless a phase already failed."""

    def __init__(self, root: str, ndrives: int, extra_args=(),
                 extra_env=None):
        self.root = root
        self.drives = [os.path.join(root, f"d{i}")
                       for i in range(1, ndrives + 1)]
        self.log_path = os.path.join(root, "server.log")
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        env = dict(os.environ)
        # Where the caller set JAX_PLATFORMS (the CPU rehearsal) the child
        # inherits it; otherwise JAX must find the chip or raise.
        env.setdefault("JAX_PLATFORMS", "tpu")
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(CHECKOUT, ".jax_cache"))
        # The scanner would heal the shards this script removes on its
        # own schedule; the heal under test is the one it asks for.
        env["MTPU_SCANNER"] = "0"
        env.update(extra_env or {})
        self.cache_dir = env["JAX_COMPILATION_CACHE_DIR"]
        os.makedirs(root, exist_ok=True)
        with open(self.log_path, "ab") as log:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "minio_tpu.server", "--drives",
                 f"{root}/d{{1...{ndrives}}}", "--port", str(self.port),
                 *extra_args],
                cwd=CHECKOUT, env=env, stdout=log, stderr=subprocess.STDOUT)
        self.client = S3Client(f"http://127.0.0.1:{self.port}",
                               "minioadmin", "minioadmin")

    def log_tail(self, nbytes: int = 6000) -> str:
        with open(self.log_path, "rb") as f:
            f.seek(max(0, os.path.getsize(self.log_path) - nbytes))
            return f.read().decode("utf-8", "replace")

    def wait_ready(self, timeout: float = 600.0) -> float:
        t0 = time.monotonic()
        url = f"http://127.0.0.1:{self.port}/minio/health/ready"
        while time.monotonic() - t0 < timeout:
            rc = self.proc.poll()
            need(rc is None, f"the server exited during boot (rc={rc}):\n"
                             f"{self.log_tail()}")
            try:
                with urllib.request.urlopen(url, timeout=2) as r:
                    if r.status == 200:
                        return time.monotonic() - t0
            except OSError:
                pass                      # still booting
            time.sleep(0.2)
        raise SmokeFailure(f"server not ready after {timeout:.0f} s:\n"
                           f"{self.log_tail()}")

    def stop(self) -> int:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
        return self.proc.returncode

    def kill(self) -> None:
        """Leave no process behind.  Reached with the server still up
        only when a phase failed: then its log is the evidence."""
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)
            print(f"--- server log (tail) ---\n{self.log_tail(20000)}",
                  file=sys.stderr)

    def boot_line(self) -> str:
        with open(self.log_path, encoding="utf-8", errors="replace") as f:
            return next((ln.strip() for ln in f
                         if ln.startswith("minio_tpu: device ")), "")

    # -- what the server says about itself ------------------------------------

    def device(self) -> dict:
        st, _, data = self.client.request("GET",
                                          "/minio/admin/v3/healthinfo")
        need(st == 200, f"healthinfo: HTTP {st}")
        (doc,) = json.loads(data)["nodes"].values()
        return doc["device"]

    def metrics(self) -> dict[str, float]:
        """{'name{labels}': value} of /minio/v2/metrics/node."""
        st, _, data = self.client.request("GET", "/minio/v2/metrics/node")
        need(st == 200, f"metrics: HTTP {st}")
        out = {}
        for line in data.decode().splitlines():
            m = re.match(r"^(mtpu_\w+(?:\{[^}]*\})?) (\S+)$", line)
            if m:
                out[m.group(1)] = float(m.group(2))
        return out


def counter(metrics: dict, name: str) -> float:
    """Sum of a family over its label sets (0 when it never counted)."""
    return sum(v for k, v in metrics.items()
               if k == name or k.startswith(name + "{"))


# -- on-disk truth -------------------------------------------------------------

def shard_files(srv: Server, key: str) -> dict[int, tuple[str, object]]:
    """{1-based shard index: (object dir on that drive, FileInfo)} for the
    drives that hold `key`, in drive order."""
    out = {}
    for d in srv.drives:
        odir = os.path.join(d, BUCKET, key)
        meta = os.path.join(odir, "xl.meta")
        if os.path.exists(meta):
            with open(meta, "rb") as f:
                fi = XLMeta.from_bytes(f.read()).latest(BUCKET, key)
            out[fi.erasure.index] = (odir, fi)
    return out


def erasure_set_of(srv: Server, key: str, set_drive_count: int) -> int:
    """Which erasure set holds `key`: drives are cut into sets in order."""
    odir = next(iter(shard_files(srv, key).values()))[0]
    drive = os.path.dirname(os.path.dirname(odir))
    return srv.drives.index(drive) // set_drive_count


def part_bytes(odir: str, fi, part: int = 1) -> bytes:
    with open(os.path.join(odir, fi.data_dir, f"part.{part}"), "rb") as f:
        return f.read()


def shard_digests(srv: Server, key: str) -> dict[int, str]:
    """{shard index: sha256 of every part file of that shard}."""
    out = {}
    for idx, (odir, fi) in shard_files(srv, key).items():
        h = hashlib.sha256()
        for p in fi.parts:
            h.update(part_bytes(odir, fi, p.number))
        out[idx] = h.hexdigest()
    return out


def check_against_reference(srv: Server, key: str, body: bytes, k: int,
                            m: int) -> int:
    """Every frame of every shard of a single-part object equals what the
    plain reference computes from the source bytes: data and parity rows
    by ReedSolomonCPU (numpy GF(2^8)), frame digests by ops/mxhash.mxh256
    (exact-integer numpy).  Returns the number of frames compared."""
    files = shard_files(srv, key)
    need(sorted(files) == list(range(1, k + m + 1)),
         f"{key}: shards on disk {sorted(files)}, want 1..{k + m}")
    fi = files[1][1]
    need((fi.erasure.data_blocks, fi.erasure.parity_blocks) == (k, m),
         f"{key}: stored as EC:{fi.erasure.data_blocks}+"
         f"{fi.erasure.parity_blocks}, want {k}+{m}")
    need(fi.erasure.bitrot_algo() == "mxh256",
         f"{key}: bitrot algo {fi.erasure.bitrot_algo()}")
    disk = {i: part_bytes(odir, f) for i, (odir, f) in files.items()}
    rs = ReedSolomonCPU(k, m)
    pos = frames = 0
    for off in range(0, len(body), BLOCK):
        rows = rs.encode_data(body[off:off + BLOCK])      # k+m arrays
        s = rows[0].size
        for i, row in enumerate(rows, start=1):
            frame = disk[i][pos:pos + HASH + s]
            need(frame[HASH:] == row.tobytes(),
                 f"{key}: shard {i} block {off // BLOCK}: bytes differ "
                 f"from ReedSolomonCPU")
            need(frame[:HASH] == mxh256(row.tobytes()),
                 f"{key}: shard {i} block {off // BLOCK}: digest differs "
                 f"from mxh256")
            frames += 1
        pos += HASH + s
    need(all(len(b) == pos for b in disk.values()),
         f"{key}: shard files longer than their frames")
    return frames


# -- phases --------------------------------------------------------------------

class Run:
    """One server's worth of phases.  `objects` remembers what was
    written: key -> (size, sha256 hex, etag)."""

    def __init__(self, srv: Server, seed: int, headers: dict, log):
        self.srv, self.seed, self.headers, self.log = srv, seed, headers, log
        self.cli = srv.client
        self.objects: dict[str, tuple[int, str, str]] = {}
        self.put_bytes = self.get_bytes = 0
        self.phases: list[dict] = []

    def phase(self, name: str, seconds: float, nbytes: int = 0, **more):
        row = {"phase": name, "seconds": round(seconds, 4), "bytes": nbytes,
               **more}
        if nbytes and seconds > 0:
            row["client_GBps"] = round(nbytes / seconds / 1e9, 4)
        self.phases.append(row)
        self.log("phase " + " ".join(f"{k}={v}" for k, v in row.items()))

    def put(self, key: str, size: int, stream: bool = False) -> float:
        """One PUT; the seconds it took.  `put_bytes` counts what goes
        through the codec, which everything but the inline object does."""
        body = body_for(self.seed, key, size)
        t0 = time.monotonic()
        if stream:
            h = self.cli.put_object_stream(BUCKET, key, io.BytesIO(body),
                                           size, headers=self.headers)
        else:
            h = self.cli.put_object(BUCKET, key, body, headers=self.headers)
        dt = time.monotonic() - t0
        self.objects[key] = (size, hashlib.sha256(body).hexdigest(),
                             h.get("ETag", "").strip('"'))
        self.put_bytes += size
        return dt

    def put_series(self, label: str, size: int, n: int,
                   stream: bool = False) -> None:
        """n sequential PUTs of one shape: the first holds whatever that
        shape compiles, the rest are the steady ones."""
        times = [self.put(f"{label}-{i}", size, stream) for i in range(n)]
        steady = statistics.median(times[1:]) if n > 1 else times[0]
        self.phase(f"put_{label}", sum(times), n * size, n=n,
                   first_s=round(times[0], 4), steady_median_s=round(steady, 4),
                   steady_GBps=round(size / steady / 1e9, 4))

    def put_multipart(self, key: str, part_size: int, nparts: int) -> None:
        import xml.etree.ElementTree as ET
        st, _, data = self.cli.request("POST", f"/{BUCKET}/{key}",
                                       query={"uploads": ""},
                                       headers=self.headers)
        need(st == 200, f"initiate multipart: HTTP {st} {data[:200]!r}")
        uid = next(e.text for e in ET.fromstring(data).iter()
                   if e.tag.endswith("UploadId"))
        whole = hashlib.sha256()
        times, parts = [], []
        for n in range(1, nparts + 1):
            body = body_for(self.seed, f"{key}#{n}", part_size)
            whole.update(body)
            t0 = time.monotonic()
            parts.append((n, self.cli.upload_part(BUCKET, key, uid, n, body)))
            times.append(time.monotonic() - t0)
        t0 = time.monotonic()
        self.cli.complete_multipart(BUCKET, key, uid, parts)
        t_done = time.monotonic() - t0
        etag = self.cli.head_object(BUCKET, key).get("ETag", "")
        size = part_size * nparts
        self.objects[key] = (size, whole.hexdigest(), etag.strip('"'))
        self.put_bytes += size
        steady = statistics.median(times[1:]) if nparts > 1 else times[0]
        self.phase("put_multipart", sum(times) + t_done, size, parts=nparts,
                   part_MiB=part_size // MIB, first_s=round(times[0], 4),
                   steady_median_s=round(steady, 4),
                   steady_GBps=round(part_size / steady / 1e9, 4),
                   complete_s=round(t_done, 4))

    def put_concurrent(self, label: str, size: int, per_client: int) -> None:
        """CLIENTS clients at once, so the coalescer has something to
        pack; each streams its objects (UNSIGNED-PAYLOAD)."""
        def one(c: int) -> None:
            for i in range(per_client):
                self.put(f"{label}-c{c}-{i}", size, stream=True)
        t0 = time.monotonic()
        with cf.ThreadPoolExecutor(CLIENTS) as pool:
            for f in [pool.submit(one, c) for c in range(CLIENTS)]:
                f.result()
        self.phase(f"put_{label}", time.monotonic() - t0,
                   CLIENTS * per_client * size, clients=CLIENTS,
                   n=CLIENTS * per_client)

    def get_all(self, skip: tuple[str, ...] = ()) -> None:
        """GET every object back whole, CLIENTS at a time; sha256 of what
        came back must equal the source's."""
        keys = [k for k in self.objects if k not in skip]

        def one(key: str) -> int:
            size, want, _ = self.objects[key]
            got = self.cli.get_object(BUCKET, key)
            need(len(got) == size
                 and hashlib.sha256(got).hexdigest() == want,
                 f"GET {key}: {len(got)} bytes, sha256 differs from source")
            return size
        t0 = time.monotonic()
        with cf.ThreadPoolExecutor(CLIENTS) as pool:
            total = sum(pool.map(one, keys))
        self.get_bytes += total
        self.phase("get_whole", time.monotonic() - t0, total, n=len(keys),
                   clients=CLIENTS)

    def get_ranged(self, key: str, lo: int, hi: int, src: bytes) -> None:
        t0 = time.monotonic()
        got = self.cli.get_object(BUCKET, key, range_=(lo, hi))
        dt = time.monotonic() - t0
        need(got == src[lo:hi + 1], f"ranged GET {key} [{lo},{hi}] differs")
        self.phase("get_ranged", dt, len(got), key=key, range=f"{lo}-{hi}")

    def get_with_shards_gone(self, key: str, gone: tuple[int, ...]) -> None:
        """Move the part files of shards `gone` (1-based, a data shard
        among them) away, GET byte-exact, and see in the server's
        counters that the rows were rebuilt (for a pattern not seen
        before, unless a hedged read happened to choose the same rows
        twice) by a program that was built before the request came:
        nothing compiled.  The files go back afterwards."""
        size, want, _ = self.objects[key]
        files = shard_files(self.srv, key)
        parts = [os.path.join(files[i][0], files[i][1].data_dir, "part.1")
                 for i in gone]
        for p in parts:
            os.rename(p, p + ".away")
        try:
            m0 = self.srv.metrics()
            t0 = time.monotonic()
            got = self.cli.get_object(BUCKET, key)
            dt = time.monotonic() - t0
            m1 = self.srv.metrics()
        finally:
            for p in parts:
                os.rename(p + ".away", p)
        need(len(got) == size and hashlib.sha256(got).hexdigest() == want,
             f"GET {key} with shards {gone} gone: bytes differ from source")
        grew = {n: counter(m1, f"mtpu_{n}_total")
                - counter(m0, f"mtpu_{n}_total")
                for n in ("healthy_reads", "decode_blocks",
                          "decode_patterns", "jit_compiles")}
        need(grew.pop("decode_patterns") <= 1
             and grew == {"healthy_reads": 0, "jit_compiles": 0,
                          "decode_blocks": size // BLOCK},
             f"GET {key} with shards {gone} gone: counters grew by {grew}")
        self.get_bytes += size
        self.phase("get_shards_gone", dt, size, key=key,
                   shards_gone=",".join(map(str, gone)))

    def degraded_then_heal(self, key: str) -> None:
        """Drop the object from the drives holding data shards 1 and 2
        (BASELINE config 3), GET it byte-exact, heal (config 4), and see
        the very same shard files come back."""
        size, want, _ = self.objects[key]
        before = shard_digests(self.srv, key)
        files = shard_files(self.srv, key)
        for i in (1, 2):
            shutil.rmtree(files[i][0])
        m0 = self.srv.metrics()
        t0 = time.monotonic()
        got = self.cli.get_object(BUCKET, key)
        dt = time.monotonic() - t0
        need(len(got) == size and hashlib.sha256(got).hexdigest() == want,
             f"degraded GET {key}: bytes differ from source")
        m1 = self.srv.metrics()
        need(counter(m1, "mtpu_healthy_reads_total")
             == counter(m0, "mtpu_healthy_reads_total"),
             "the GET with two data shards gone was served by the healthy "
             "verify-only path")
        self.get_bytes += size
        self.phase("get_degraded", dt, size, key=key, shards_gone="1,2")

        t0 = time.monotonic()
        st, _, data = self.cli.request(
            "POST", "/minio/admin/v3/heal",
            query={"bucket": BUCKET, "prefix": key})
        need(st == 200, f"admin heal: HTTP {st} {data[:200]!r}")
        while True:
            st, _, data = self.cli.request("GET", "/minio/admin/v3/heal")
            seq = [s for s in json.loads(data)["sequences"]
                   if s["prefix"] == key]
            need(st == 200 and seq, f"heal status: HTTP {st}")
            if seq[0]["state"] in ("done", "failed", "stopped"):
                break
            need(time.monotonic() - t0 < 300, "heal did not finish in 300 s")
            time.sleep(0.1)
        dt = time.monotonic() - t0
        need(seq[0]["state"] == "done", f"heal ended {seq[0]}")
        after = shard_digests(self.srv, key)
        need(after == before,
             f"healed shards differ: {sorted(set(before) - set(after))} "
             f"missing, "
             f"{[i for i in after if before.get(i) != after[i]]} changed")
        got = self.cli.get_object(BUCKET, key)
        need(hashlib.sha256(got).hexdigest() == want,
             f"GET after heal {key}: bytes differ from source")
        self.phase("heal", dt, size, key=key, shards_back="1,2")


def check_counters(run: Run, m0: dict, m1: dict, lanes: int, log) -> dict:
    """The bytes written crossed to the device, on every lane asked of,
    and nothing fell back.  These count on a CPU backend too, which is
    why the device block is checked first."""
    row = {
        "h2d_bytes": counter(m1, "mtpu_h2d_bytes_total")
        - counter(m0, "mtpu_h2d_bytes_total"),
        "lane_dispatches": {
            str(d): counter(
                m1, f'mtpu_device_lane_dispatches_total{{device="{d}"}}')
            for d in range(lanes)},
        "encode_blocks": {
            plane: counter(
                m1, f'mtpu_encode_blocks_total{{plane="{plane}"}}')
            - counter(m0, f'mtpu_encode_blocks_total{{plane="{plane}"}}')
            for plane in ("lane", "mesh", "host")},
        "coalesce_items": counter(m1, "mtpu_coalesce_items_total"),
        "coalesce_dispatches": counter(m1, "mtpu_coalesce_dispatches_total"),
        **{n: counter(m1, n) for n in FALLBACK_COUNTERS},
    }
    log("counters " + json.dumps(row))
    for n in FALLBACK_COUNTERS:
        need(row[n] == 0, f"{n} = {row[n]}: a fallback or fault path ran")
    for d, v in row["lane_dispatches"].items():
        need(v > 0, f"device lane {d} dispatched nothing")
    return row


def one_chip(args, root: str, log) -> dict:
    """EC:8+4 over 12 drives on one chip: the main path, end to end."""
    k, m = 8, 4
    small = args.small
    srv = Server(root, 12)
    try:
        boot_s = srv.wait_ready()
        dev = srv.device()
        log(f"device {json.dumps(dev)} boot_s={boot_s:.1f} "
            f"compile_cache={srv.cache_dir}")
        boot_line = srv.boot_line()
        need(boot_line, "the server printed no device boot line")
        log(f"server said: {boot_line}")
        need(dev["count"] == 1,
             f"one chip asked for, the server sees {dev['count']}")
        need(dev["in_process"], "the serving process holds no JAX backend")
        run = Run(srv, args.seed, {"x-amz-storage-class": "STANDARD"}, log)
        run.phase("boot", boot_s)
        cli = srv.client

        def set_parity(parity: int) -> None:
            """The STANDARD class through the admin config route."""
            st, _, data = cli.request(
                "POST", "/minio/admin/v1/config",
                body=json.dumps({"subsys": "storage_class",
                                 "key": "standard",
                                 "value": f"EC:{parity}"}).encode())
            need(st == 200, f"config set: HTTP {st} {data[:200]!r}")

        set_parity(m)                    # EC:8+4 on the 12 drives
        cli.make_bucket(BUCKET)

        # 4 KiB: inline in xl.meta, never reaches the codec.
        m0 = srv.metrics()
        dt = run.put("inline-4k", 4096)
        m_inline = srv.metrics()
        need(counter(m_inline, "mtpu_device_lane_dispatches_total")
             == counter(m0, "mtpu_device_lane_dispatches_total")
             and counter(m_inline, "mtpu_h2d_bytes_total")
             == counter(m0, "mtpu_h2d_bytes_total"),
             "the 4 KiB PUT reached the device codec")
        for odir, fi in shard_files(srv, "inline-4k").values():
            need(fi.inline_data is not None and os.listdir(odir)
                 == ["xl.meta"], "the 4 KiB object is not inline")
        run.phase("put_inline_4KiB", dt, 4096)
        run.put_bytes = 0                # codec bytes start here

        run.put_series("1MiB", MIB, 3 if small else 16)
        run.put_series("10MiB", 10 * MIB, 2 if small else 4)
        if small:
            run.put_multipart("multipart", 6 * MIB, 4)
            run.put_concurrent("bulk", 8 * MIB, 1)
            run.put("victim", 8 * MIB, stream=True)
        else:
            # BASELINE config 2: 256 MiB multipart in 64 MiB parts.
            run.put_multipart("multipart", 64 * MIB, 4)
            run.put_concurrent("bulk", 64 * MIB, 3)
            run.put("victim", 64 * MIB, stream=True)
        need(small or run.put_bytes >= 1 << 30,
             f"only {run.put_bytes} bytes written, want >= 1 GiB")

        # `victim` is first read degraded, so that no cache can answer.
        run.get_all(skip=("victim",))
        run.get_ranged("10MiB-0", 3 * MIB - 5, 7 * MIB + 9,
                       body_for(args.seed, "10MiB-0", 10 * MIB))
        run.degraded_then_heal("victim")

        # Reads that rebuild rows with the geometry's one decode
        # program (PR 35), at warp's 10 MiB: one and two data shards of
        # 8+4; then the same 12 drives at EC:6, where K divides no
        # block: one shard, three, and all six data shards gone.
        lost = 3 * MIB if small else 10 * MIB
        run.put("lost-8p4", lost)
        for gone in ((1,), (1, 2)):
            run.get_with_shards_gone("lost-8p4", gone)
        set_parity(6)
        run.put("lost-6p6", lost)
        frames = check_against_reference(
            srv, "lost-6p6", body_for(args.seed, "lost-6p6", lost), 6, 6)
        run.phase("reference_check", 0.0, lost, key="lost-6p6",
                  frames=frames)
        for gone in ((1,), (2, 5, 9), (1, 2, 3, 4, 5, 6)):
            run.get_with_shards_gone("lost-6p6", gone)

        m1 = srv.metrics()
        counters = check_counters(run, m0, m1, 1, log)
        # Every PUT byte is a K-row upload (sizes are whole blocks and
        # K*S = 1 MiB), every first healthy GET uploads its rows again
        # to verify them, and a dispatch holds at most 64 blocks.
        # (Off the chip the host codec serves and nothing crosses: the
        # rehearsal goes on, and fails at its end for want of a chip.)
        floor = run.put_bytes + run.get_bytes
        need(counters["h2d_bytes"] >= floor or dev["platform"] != "tpu",
             f"h2d bytes {counters['h2d_bytes']:.0f} < {floor} written+read")
        need(counters["lane_dispatches"]["0"] >= run.put_bytes / (64 * MIB),
             "fewer device dispatches than the bytes written need")

        t0 = time.monotonic()
        frames = check_against_reference(
            srv, "10MiB-0", body_for(args.seed, "10MiB-0", 10 * MIB), k, m)
        run.phase("reference_check", time.monotonic() - t0, 10 * MIB,
                  key="10MiB-0", frames=frames,
                  reference="ReedSolomonCPU+mxh256 (numpy)")

        rc = srv.stop()
        need(rc == 0, f"the server exited {rc} on SIGTERM:\n{srv.log_tail()}")
        run.phase("stop", 0.0, rc=rc)
        return {"device": dev, "phases": run.phases, "counters": counters,
                "put_bytes": run.put_bytes, "get_bytes": run.get_bytes}
    finally:
        srv.kill()


def four_chip_requests(args, srv: Server, log, lanes: int,
                       plane: str) -> dict:
    """The requests every four-chip server gets; `plane` is where the
    PUTs' parity must have been computed.  Returns what must be equal
    between the servers: ETags and per-shard file digests."""
    small = args.small
    dev = srv.device()
    run = Run(srv, args.seed, {}, log)
    srv.client.make_bucket(BUCKET)
    m0 = srv.metrics()
    size = 2 * MIB if small else 8 * MIB
    run.put_concurrent("obj", size, 4 if small else 8)
    per_set = [0, 0, 0, 0]
    for key in run.objects:
        per_set[erasure_set_of(srv, key, 4)] += 1
    log(f"objects per erasure set: {per_set}")
    need(all(per_set), f"an erasure set got no object: {per_set}")
    victim = sorted(run.objects)[0]
    run.get_all(skip=(victim,))
    run.degraded_then_heal(victim)
    on_chip = dev["platform"] == "tpu"
    # (The rehearsal's host codec verifies a healthy GET off the lanes
    # when the mesh is forced, and computes a lane's parity itself.)
    counters = check_counters(run, m0, srv.metrics(),
                              lanes if on_chip or plane != "mesh" else 0,
                              log)
    if not on_chip and plane == "lane":
        plane = "host"
    want = {p: run.put_bytes // MIB if p == plane else 0
            for p in ("lane", "mesh", "host")}
    need(counters["encode_blocks"] == want,
         f"PUT parity by plane {counters['encode_blocks']}, want {want}")
    key = sorted(run.objects)[1]
    t0 = time.monotonic()
    frames = check_against_reference(
        srv, key, body_for(args.seed, key, size), 2, 2)
    run.phase("reference_check", time.monotonic() - t0, size, key=key,
              frames=frames, reference="ReedSolomonCPU+mxh256 (numpy)")
    shards = {key: shard_digests(srv, key) for key in run.objects}
    rc = srv.stop()
    need(rc == 0, f"the server exited {rc} on SIGTERM:\n{srv.log_tail()}")
    return {"device": dev, "phases": run.phases, "counters": counters,
            "etags": {k: v[2] for k, v in run.objects.items()},
            "shards": shards}


def four_chips(args, root: str, log) -> dict:
    """Four EC:2+2 sets over 16 drives.  Left to itself the server gives
    every chip the set it owns (`mesh_rule`: sets >= chips): set i's PUT
    encode, GET digests and degraded decode ride lane i % 4.  Then, one
    after the other, since the chips belong to one process at a time,
    the same requests with the mesh forced (PUT parity SPMD over all
    four chips, the degraded GET through its all-gather) and on one
    lane with the mesh off, and every ETag and shard file must agree
    between the three."""
    servers = (
        ("four_sets_four_lanes", {}, 4, "lane"),
        ("four_sets_mesh_forced", {"MTPU_MESH": "1"}, 4, "mesh"),
        ("one_lane_no_mesh", {"MTPU_DEVICES": "1", "MTPU_MESH": "0"}, 1,
         "lane"))
    results = {}
    for name, env, lanes, plane in servers:
        srv = Server(os.path.join(root, name), 16,
                     extra_args=("--set-drive-count", "4"), extra_env=env)
        try:
            boot_s = srv.wait_ready()
            dev = srv.device()
            log(f"[{name}] device {json.dumps(dev)} boot_s={boot_s:.1f}")
            need(dev["count"] == 4,
                 f"four devices asked for, the server sees {dev['count']}")
            need(dev["lanes"] == lanes, f"lanes {dev['lanes']}, want {lanes}")
            results[name] = four_chip_requests(
                args, srv, lambda s, n=name: log(f"[{n}] {s}"), lanes, plane)
        finally:
            srv.kill()
    oracle = results["one_lane_no_mesh"]
    for name in ("four_sets_four_lanes", "four_sets_mesh_forced"):
        r = results[name]
        need(r["etags"] == oracle["etags"],
             f"ETags differ between {name} and the one-lane oracle")
        differ = [k for k, v in r["shards"].items()
                  if v != oracle["shards"].get(k)]
        need(r["shards"] == oracle["shards"],
             f"on-disk shard files differ between {name} and the one-lane "
             f"oracle: {differ}")
    log(f"oracle: {len(oracle['etags'])} ETags and "
        f"{sum(len(v) for v in oracle['shards'].values())} shard files equal "
        f"between four lanes, the forced mesh and MTPU_DEVICES=1 MTPU_MESH=0")
    for r in results.values():
        del r["shards"], r["etags"]
    return {"device": results["four_sets_four_lanes"]["device"], **results}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=22,
                    help="every object's bytes are made from it")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-chip path and its oracle")
    ap.add_argument("--small", action="store_true",
                    help="rehearsal sizes (for JAX_PLATFORMS=cpu)")
    args = ap.parse_args()

    def log(s: str) -> None:
        print(s, flush=True)

    root = tempfile.mkdtemp(prefix="mtpu_smoke_")
    t0 = time.monotonic()
    try:
        result = (four_chips if args.chips == 4 else one_chip)(
            args, root, log)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    need("jax" not in sys.modules, "this script imported jax")
    result["seconds"] = round(time.monotonic() - t0, 1)
    out_dir = os.path.join(CHECKOUT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"chip_smoke_{args.chips}chip.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    dev = result["device"]
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no chip: every phase ran, but the server "
              f"computes on platform={dev['platform']!r}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
