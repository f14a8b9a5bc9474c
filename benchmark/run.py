#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The parent of a run.  It never imports JAX: the one process that does is the
serving child, `benchmark/serve.py`, which is the program's normal entry point
(`minio_tpu.server.__main__.main`) with a profiler around it.  Load comes from
client processes (`benchmark/loadgen.py`), one connection each.

Drive directories and everything else a run makes live in a fresh directory
in RAM, inside the places a run may write: under TMPDIR where that is set and
a tmpfs, else on a tmpfs of the run's own mounted over `<checkout>/.bench_run`
in a private mount namespace (`ram_backed_dir`).  A run writes nothing to the
machine's disk but the compile cache, and nothing outside its checkout, HOME
and TMPDIR.

Phases: boot -> configure -> prefill -> where the mix says `hide_shards`,
that many data shards of every prefilled object are taken off the drives
(`hide_shards`) -> warm-up (every kind of request the
cell sends, at the cell's concurrency) -> barrier -> window of --seconds ->
drain (requests begun in the window are waited for) -> checks of what the
window wrote and read (`benchmark/reference.py`) -> SIGTERM, exit code 0
required -> detail lines on stderr, the contract's line last on stdout.
`setup_s` is everything from this process's start to the barrier.  A failed
phase fails the run; a run that finds no TPU ends non-zero and prints no
result line.

The cell, its configuration and its per-layer metrics are data
(`benchmark/traffic/`, `configs/`, `metrics/`), found by the names in
`BENCHMARK.json`; see `benchmark/README.md`.
"""

from __future__ import annotations

import argparse
import ctypes
import glob
import itertools
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import reference  # noqa: E402
import trace_reduce  # noqa: E402  (imports jax only where it reads a trace)
import traffic  # noqa: E402
import work  # noqa: E402
from s3client import S3Client  # noqa: E402

T_START = time.monotonic()      # "process start": the imports above are ~0.1 s

BUCKET = "bench"
MIB = 1 << 20
FALLBACK_COUNTERS = ("mtpu_coalesce_fallbacks_total",
                     "mtpu_coalesce_batch_faults_total",
                     "mtpu_ipc_dispatch_fallbacks_total")


class RunFailure(Exception):
    pass


def need(cond, msg: str) -> None:
    if not cond:
        raise RunFailure(msg)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def percentile(values: list[float], q: float) -> float:
    """The q-th percentile (0..100), linear between the two nearest ranks."""
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


RUN_DIR = os.path.join(CHECKOUT, ".bench_run")     # listed in .gitignore
MS_NOSUID, MS_NODEV, MS_REC, MS_PRIVATE = 2, 4, 1 << 14, 1 << 18


def fs_type(path: str) -> str:
    """The type of the file system that holds `path`, from /proc/mounts."""
    real = os.path.realpath(path)
    best = ("", "")
    with open("/proc/mounts") as f:
        for line in f:
            _, where, fstype = line.split()[:3]
            under = real == where or real.startswith(where.rstrip("/") + "/")
            if under and len(where) >= len(best[0]):     # the last one wins
                best = (where, fstype)
    return best[1]


def _mount(source: bytes, target: str, fstype: bytes | None, flags: int):
    libc = ctypes.CDLL(None, use_errno=True)
    if libc.mount(source, target.encode(), fstype, flags, None) != 0:
        err = ctypes.get_errno()
        raise OSError(err, os.strerror(err), target)


def ram_backed_dir() -> str:
    """The directory that a run's own directory is made in.  It is RAM, and
    it is inside what a run may write (its checkout, HOME, TMPDIR).

    RAM because the drives of a cell that writes 0.4 GB/s must not be the
    sealed machine's 9p share of its host's disk: write-back throttling
    there swings such a cell threefold, and the host keeps every block once
    written, 12 GB a run (PERF.md, PR 26).  So: TMPDIR, where it is set and
    a tmpfs.  Else a tmpfs mounted over `<checkout>/.bench_run` after this
    process has left its parent's mount namespace: the path is inside the
    checkout, only this run's processes see the mount, and the kernel frees
    it when the last of them has gone, however they ended.  That needs the
    right to mount (root, as on the chip's machine).  Else the run fails:
    it never writes the drives to a disk, and never to /dev/shm."""
    tmp = os.environ.get("TMPDIR")
    if tmp and fs_type(tmp) in ("tmpfs", "ramfs") and os.access(tmp, os.W_OK):
        return os.path.realpath(tmp)
    os.makedirs(RUN_DIR, exist_ok=True)
    if fs_type(RUN_DIR) == "tmpfs":
        return RUN_DIR              # mounted by an earlier run of this process
    try:
        os.unshare(os.CLONE_NEWNS)
        # Nothing mounted from here on reaches the namespace we came from.
        _mount(b"none", "/", None, MS_REC | MS_PRIVATE)
        _mount(b"tmpfs", RUN_DIR, b"tmpfs", MS_NOSUID | MS_NODEV)
    except OSError as e:
        raise RunFailure(
            f"no RAM for the drives: TMPDIR={tmp!r} is no writable tmpfs, "
            f"and a private tmpfs cannot be mounted over {RUN_DIR} ({e}). "
            f"Give the run a TMPDIR on a tmpfs, or the right to mount.")
    return RUN_DIR


# -- the serving child -----------------------------------------------------------

class Server:
    """`python benchmark/serve.py ...` over fresh drive directories: the only
    process of the run that may touch the chip.  Always stopped."""

    def __init__(self, root: str, cfg: dict, trace: bool,
                 serve_script: str):
        self.root, self.cfg = root, cfg
        n = cfg["drives"]
        self.drives = [os.path.join(root, f"d{i}") for i in range(1, n + 1)]
        self.ctl = os.path.join(root, "ctl")
        os.makedirs(self.ctl)
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
        env.pop("MTPU_WORKERS", None)        # one serving process
        # JAX says in the server's log what it compiles and how long that
        # took: `compiles_between` finds what compiled inside the window.
        env.setdefault("JAX_LOG_COMPILES", "1")
        env.update(cfg["env"])
        # The digest the configuration states (`traffic.load_cell`: mxh256,
        # the program's own default, where it states none).
        env["MTPU_BITROT_ALGO"] = cfg["bitrot_algo"]
        with open(self.log_path, "ab") as out:
            self.proc = subprocess.Popen(
                [sys.executable, serve_script, self.ctl,
                 "1" if trace else "0",
                 "--drives", f"{root}/d{{1...{n}}}", "--port", str(self.port),
                 *cfg["server_args"]],
                cwd=CHECKOUT, env=env, stdout=out, stderr=subprocess.STDOUT)
        self.client = S3Client("127.0.0.1", self.port)

    def log_tail(self, nbytes: int = 6000) -> str:
        with open(self.log_path, "rb") as f:
            f.seek(max(0, os.path.getsize(self.log_path) - nbytes))
            return f.read().decode("utf-8", "replace")

    def compiles_between(self, start: int, end: int) -> list[list]:
        """[program and argument shapes, seconds] of every XLA compilation
        (or load from the compile cache) that the log reports between two
        of its sizes."""
        with open(self.log_path, "rb") as f:
            f.seek(start)
            text = f.read(end - start).decode("utf-8", "replace")
        shapes, out = {}, []
        for name, args, done, secs in re.findall(
                r"Compiling (\S+) with global shapes and types \((.*?)\)\. "
                r"Argument|Finished XLA compilation of (\S+) in ([\d.e+-]+) s",
                text):
            if name:
                shapes[name] = re.sub(r"ShapedArray\(([^()]*)\)", r"\1", args)
            else:
                out.append([f"{done} {shapes.get(done, '')}".strip(),
                            float(secs)])
        return out

    def wait_ready(self, timeout: float = 900.0) -> None:
        url = f"http://127.0.0.1:{self.port}/minio/health/ready"
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            rc = self.proc.poll()
            need(rc is None, f"the server exited during boot (rc={rc}):\n"
                             f"{self.log_tail()}")
            try:
                with urllib.request.urlopen(url, timeout=2) as r:
                    if r.status == 200:
                        return
            except OSError:
                pass                          # still booting
            time.sleep(0.1)
        raise RunFailure(f"server not ready after {timeout:.0f} s:\n"
                         f"{self.log_tail()}")

    def ask(self, request: str, answer: str, timeout: float = 120.0) -> dict:
        """One exchange with serve.py's control thread."""
        path = os.path.join(self.ctl, answer)
        if os.path.exists(path):
            os.unlink(path)
        open(os.path.join(self.ctl, request), "w").close()
        t0 = time.monotonic()
        while not os.path.exists(path):
            need(self.proc.poll() is None,
                 f"the server died waiting for {answer}:\n{self.log_tail()}")
            need(time.monotonic() - t0 < timeout,
                 f"no {answer} from the server after {timeout:.0f} s")
            time.sleep(0.01)
        with open(path) as f:
            return json.load(f)

    def healthinfo_device(self) -> dict:
        st, _, data = self.client.request("GET", "/minio/admin/v3/healthinfo")
        need(st == 200, f"healthinfo: HTTP {st}")
        (doc,) = json.loads(data)["nodes"].values()
        return doc["device"]

    def configure(self) -> None:
        value = self.cfg["storage_class_standard"]
        if value:
            st, _, data = self.client.request(
                "POST", "/minio/admin/v1/config",
                body=json.dumps({"subsys": "storage_class", "key": "standard",
                                 "value": value}).encode())
            need(st == 200, f"config set: HTTP {st} {data[:200]!r}")
        self.client.make_bucket(BUCKET)

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

    def cpu_seconds(self) -> float:
        """utime + stime of the serving process, all its threads."""
        with open(f"/proc/{self.proc.pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")

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
        """Leave no process behind.  Reached with the server still up only
        when a phase failed, and with it gone by itself when that failed a
        phase: either way its log is the evidence."""
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)
        if self.proc.returncode != 0:
            log(f"--- server exited {self.proc.returncode}; its log (tail) "
                f"---\n{self.log_tail(20000)}")


def counter(metrics: dict, spec: str) -> float | None:
    """Sum of a family over the label sets that `spec` admits:
    `family` or `family{label=value,...}`.  None where nothing counted."""
    family, _, rest = spec.partition("{")
    want = [f'{kv.split("=")[0]}="{kv.split("=")[1]}"'
            for kv in rest.rstrip("}").split(",") if kv]
    hits = [v for k, v in metrics.items()
            if (k == family or k.startswith(family + "{"))
            and all(w in k for w in want)]
    return sum(hits) if hits else None


# -- the client processes ----------------------------------------------------------

class Clients:
    def __init__(self, root: str, port: int, seed: int, wl: dict, cfg: dict):
        self.procs, self.records = [], []
        for c in range(wl["clients"]):
            spec = {"host": "127.0.0.1", "port": port, "bucket": BUCKET,
                    "seed": seed, "client": c, "workload": wl,
                    "put_headers": cfg["put_headers"],
                    "records": os.path.join(root, f"records-{c}.jsonl")}
            path = os.path.join(root, f"client-{c}.json")
            with open(path, "w") as f:
                json.dump(spec, f)
            self.records.append(spec["records"])
            self.procs.append(subprocess.Popen(
                [sys.executable, os.path.join(HERE, "loadgen.py"), path],
                cwd=CHECKOUT, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                text=True))

    def tell(self, line: str) -> None:
        for p in self.procs:
            p.stdin.write(line + "\n")
            p.stdin.flush()

    def gather(self) -> list[dict]:
        out = []
        for c, p in enumerate(self.procs):
            line = p.stdout.readline()
            need(line, f"client {c} died (rc={p.poll()})")
            out.append(json.loads(line))
        return out

    def all(self, line: str) -> list[dict]:
        self.tell(line)
        return self.gather()

    def read_records(self) -> list[dict]:
        recs = []
        for path in self.records:
            with open(path) as f:
                recs += [json.loads(ln) for ln in f]
        return recs

    def stop(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.stdin.write("quit\n")
                    p.stdin.close()
                    p.wait(timeout=10)
                except (OSError, subprocess.TimeoutExpired):
                    p.kill()
                    p.wait(timeout=30)


# -- what the clients' records say --------------------------------------------------

PUT_OPS = ("PUT", "PART")


def good(r: dict) -> bool:
    """Answered 2xx and, for a GET, with the bytes that were written."""
    return r["ok"] and r.get("match", True)


def client_quantities(win: list[dict], t0: float, t1: float) -> dict:
    """Sums over the window's requests (begun in it, answered whenever):
    what the per-layer ratios divide by."""
    put = sum(r["bytes"] for r in win if good(r) and r["op"] in PUT_OPS)
    get = sum(r["bytes"] for r in win if good(r) and r["op"] == "GET")
    return {"put_bytes": put, "get_bytes": get, "payload_bytes": put + get,
            "payload_blocks": (put + get) / MIB, "window_s": t1 - t0}


def end_to_end(win: list[dict], t0: float, t1: float) -> dict:
    """The end-to-end metrics: rates are the bytes answered inside
    [t0, t1] over its length, tails are over every request begun in it."""
    out = {}
    for name, ops in (("put", PUT_OPS), ("get", ("GET",))):
        mine = [r for r in win if r["op"] in ops]
        if not mine:
            continue
        inside = sum(r["bytes"] for r in mine if good(r) and r["t1"] <= t1)
        out[f"{name}_gbps"] = inside / (t1 - t0) / 1e9
        # A failed request has missed any limit: it sits at the far end.
        times = [(r["t1"] - r["t0"]) * 1e3 if good(r) else float("inf")
                 for r in mine]
        out[f"{name}_p95_ms"] = percentile(times, 95)
    return out


def timeline(win: list[dict], t0: float, t1: float, step: float = 5.0):
    """GB/s of payload answered in each `step` seconds of the window: a
    run that slows or stalls part-way shows here."""
    out = [0.0] * max(1, round((t1 - t0) / step))
    for r in win:
        if good(r) and r["t1"] <= t1 and r["op"] in PUT_OPS + ("GET",):
            out[min(int((r["t1"] - t0) // step), len(out) - 1)] += r["bytes"]
    return [round(b / step / 1e9, 4) for b in out]


def op_table(win: list[dict]) -> dict:
    out = {}
    for op in sorted({r["op"] for r in win}):
        t = [(r["t1"] - r["t0"]) * 1e3 for r in win
             if r["op"] == op and r["ok"]]
        if t:
            out[op] = {"n": len(t), "p50_ms": round(percentile(t, 50), 3),
                       "p95_ms": round(percentile(t, 95), 3),
                       "max_ms": round(max(t), 3)}
    return out


# -- correct ---------------------------------------------------------------------

def disk_files(srv: Server, key: str, part: int) -> list[bytes]:
    """The part's shard file on every drive that holds one.  A key written
    more than once may have an older data directory beside the newest for a
    moment: the newest counts."""
    files = []
    for d in srv.drives:
        found = glob.glob(os.path.join(d, BUCKET, key, "*", f"part.{part}"))
        if found:
            with open(max(found, key=os.path.getmtime), "rb") as f:
                files.append(f.read())
    return files


def sample_in_turn(rng, objects: list[tuple]) -> list[tuple]:
    """`objects` ((client, ...) tuples) in an order drawn from `rng` that
    takes the clients in turn: the first `clients` of them hold every
    client's stream."""
    per_client: dict[int, list] = {}
    for i in rng.permutation(len(objects)):
        per_client.setdefault(objects[i][0], []).append(objects[i])
    return [w for turn in itertools.zip_longest(*per_client.values())
            for w in turn if w is not None]


def first_blocks(seed: int, wl: dict) -> dict:
    """{(client, n): the first erasure block of the body} of every
    client's n-th prefilled object, made again from the seed."""
    out = {}
    for c in range(wl["clients"]):
        b = traffic.Bodies(seed, c, wl)
        for n in range(wl["prefill_per_client"]):
            tag, rest = b.chunks(traffic.new_key(c, n), 0, 0)
            out[c, n] = tag + bytes(rest[:reference.BLOCK - len(tag)])
    return out


def data_shard_files(drives: list[str], key: str, head: bytes,
                     k: int) -> dict[int, str]:
    """{data shard i (0-based): its `part.1`} of an object whose first
    erasure block is `head`.  Which drive holds which shard is the
    program's choice, so the files are told apart by content: the code is
    systematic, and the first frame of data shard i's file is [digest | row
    i of that block] (`reference.data_rows`).  A shard on no drive is left
    out."""
    rows = reference.data_rows(head, k)
    where: dict[int, str] = {}
    for d in drives:
        for path in glob.glob(os.path.join(d, BUCKET, key, "*", "part.1")):
            with open(path, "rb") as f:
                row = np.frombuffer(f.read(reference.DIGEST + rows.shape[1]),
                                    dtype=np.uint8)[reference.DIGEST:]
            where.update((i, path) for i in range(k)
                         if np.array_equal(row, rows[i]))
    return where


def hide_shards(srv: Server, seed: int, wl: dict, cfg: dict,
                heads: dict) -> dict:
    """Take `hide_shards` data shards of every prefilled object off the
    drives: their `part.1` is unlinked, xl.meta stays.  Which shards go is
    drawn from (seed, object) (`traffic.hidden_shards`).

    First the look that an acknowledged PUT is owed, on the very objects
    the window will read: the K+M shard files of a sample of them (drawn
    from the seed, clients in turn) are read whole, and `check_correct`
    holds them against the reference once the window has closed.  A drive
    without the file, or a data shard on no drive, is that look's to report
    (`shards_missing`), not this step's.

    Returns {"looked": [(client, key, files)], "paths": the files
    unlinked}."""
    k, h = cfg["data_shards"], wl["hide_shards"]
    order = sample_in_turn(np.random.default_rng([seed, 0xC0DE]),
                           sorted(heads))
    looked = []
    for c, n in order[:wl["check"]["disk_parts"]]:
        key = traffic.new_key(c, n)
        looked.append((c, key, disk_files(srv, key, 1)))
    paths = []
    for (c, n), head in sorted(heads.items()):
        where = data_shard_files(srv.drives, traffic.new_key(c, n), head, k)
        for i in traffic.hidden_shards(seed, c, n, k, h):
            if i in where:
                os.unlink(where[i])
                paths.append(where[i])
    return {"looked": looked, "paths": paths}


def check_correct(srv: Server, seed: int, wl: dict, cfg: dict,
                  win: list[dict], live: list[list], grew,
                  hidden: dict | None = None) -> tuple[bool, dict]:
    """Every number compared, beside its limit.  All are exact
    comparisons: the limit is 0 (and a floor of 1 on what was compared).
    `grew(family)` is a counter's growth over the window; `hidden` is
    what `hide_shards` returned, where the cell hides shards."""
    k, m = cfg["data_shards"], cfg["parity_shards"]
    gets = [r for r in win if r["op"] == "GET" and r["ok"]]
    nums = {
        "requests_failed": sum(not r["ok"] for r in win),
        "get_mismatch": sum(not r["match"] for r in gets),
        "fallbacks": sum(grew(n) for n in FALLBACK_COUNTERS),
    }
    if hidden is not None:
        # Every block of every GET had rows rebuilt: no read found its K
        # data shards, no cache or shortcut answered, and nothing healed
        # under the measurement.
        nums.update(
            gets_served_healthy=grew("mtpu_healthy_reads_total"),
            decode_blocks_short=max(
                0.0, len(gets) * (wl["object_bytes"] // MIB)
                - grew("mtpu_decode_blocks_total")),
            hidden_files_back=sum(os.path.exists(p)
                                  for p in hidden["paths"]))
    # Objects the window wrote whole and that are still the newest under
    # their key: a sample drawn from the seed, read back through the front
    # door and looked up on every drive.  The sample takes the clients in
    # turn, so one of `clients` objects or more holds every client's stream;
    # the parts looked up on the drives take the sampled objects in turn,
    # each time another part number, so a few of them hold every stream and
    # every part number.
    done_op = "COMPLETE" if wl["part_bytes"] else "PUT"
    written = {(r["client"], r["key"], r["gen"]) for r in win
               if r["op"] == done_op and r["ok"]}
    alive = sorted(w for w in written
                   if [w[1], w[2]] in live[w[0]])
    rng = np.random.default_rng([seed, 0xC0DE])
    order = sample_in_turn(rng, alive)
    parts = traffic.parts_of(wl)
    frames = bad_bytes = bad_digest = missing = readback_bad = 0
    nread = nparts = 0
    todo = [(*obj, parts[(i + turn) % len(parts)])
            for turn in range(len(parts)) for i, obj in enumerate(order)]
    bodies: dict[int, traffic.Bodies] = {}
    for c, key, gen in order[:wl["check"]["readback_objects"]]:
        b = bodies.setdefault(c, traffic.Bodies(seed, c, wl))
        got = srv.client.get_object(BUCKET, key)
        readback_bad += not b.matches(got, key, gen, wl)
        nread += 1
    # Where shards were hidden, the files are those read before that.
    held = [(c, key, 0, 0, files)
            for c, key, files in (hidden or {}).get("looked", [])]
    held += [(c, key, gen, p, None)
             for c, key, gen, p in todo[:wl["check"]["disk_parts"]]]
    for c, key, gen, p, files in held:
        b = bodies.setdefault(c, traffic.Bodies(seed, c, wl))
        body = b"".join(b.chunks(key, p, gen))
        res = reference.compare_part(
            body, k, m, disk_files(srv, key, max(p, 1)) if files is None
            else files, cfg["bitrot_algo"])
        frames += res["frames"]
        bad_bytes += res["bad_bytes"]
        bad_digest += res["bad_digest"]
        missing += res["shards_missing"]
        nparts += 1
    # Keys whose DELETE the window acknowledged (a new key is never written
    # twice): a GET after it must not return the object.
    gone = sorted(r["key"] for r in win if r["op"] == "DELETE" and r["ok"])
    gone = [gone[i] for i in rng.permutation(len(gone))]
    gone = gone[:wl["check"]["deleted_gets"]]
    still_served = 0
    for key in gone:
        still_served += srv.client.request("GET", f"/{BUCKET}/{key}")[0] != 404
        # The program closes the socket after an error answer and does not
        # say `Connection: close`: the next request gets a new one.
        srv.client.close()
    nums.update(readback_mismatch=readback_bad, frames_bad_bytes=bad_bytes,
                frames_bad_digest=bad_digest, shards_missing=missing,
                deleted_still_served=still_served)
    compared = {"gets_compared": len(gets), "readbacks_compared": nread,
                "disk_parts_compared": nparts, "frames_compared": frames,
                "deleted_compared": len(gone)}
    table = {name: {"value": v, "limit": 0} for name, v in nums.items()}
    ok = all(v == 0 for v in nums.values())
    for name, v in compared.items():
        # What is compared has to be there to compare.
        floor = 0 if (name == "gets_compared" and not wl["mix"].get("GET")
                      or name == "deleted_compared"
                      and not wl["mix"].get("DELETE")
                      or name == "readbacks_compared"
                      and not wl["mix"].get("PUT")) else 1
        table[name] = {"value": v, "at_least": floor}
        ok = ok and v >= floor
    return ok, table


# -- per-layer metrics -------------------------------------------------------------

def quantity(spec, q: dict) -> float | None:
    """One named quantity of the traced window: `counter:<family>[{l=v}]`
    (its growth over the window), `client:<sum>`, `proc:cpu_s`,
    `trace:busy_s`, a literal number, or a list of these, summed."""
    if isinstance(spec, list):          # a sum of those that are there
        found = [v for v in (quantity(s, q) for s in spec) if v is not None]
        return sum(found) if found else None
    kind, _, name = spec.partition(":")
    if kind == "counter":
        a, b = counter(q["metrics0"], name), counter(q["metrics1"], name)
        return None if b is None else b - (a or 0.0)
    if kind == "client":
        return q["client"][name]
    if kind == "proc":
        return q["proc"][name]
    if kind == "trace":
        return (q["trace"] or {}).get(name)
    return float(spec)


def read_metric(m: dict, q: dict, cfg: dict) -> float | None:
    """The metric as its file describes it; None where there is nothing
    to read (it is then left out of the line, never reported as 0)."""
    kind = m["kind"]
    if kind == "ratio":
        num, den = quantity(m["num"], q), quantity(m["den"], q)
        if num is None or not den:
            return None
        return m.get("scale", 1.0) * num / den
    if kind == "trace_idle":
        busy = quantity("trace:busy_s", q)
        if busy is None:
            return None
        return trace_reduce.idle_pct(busy, q["client"]["window_s"])
    if kind == "trace_roofline":
        busy = quantity("trace:busy_s", q)
        data = quantity(m["bytes"], q)
        if not busy or not data:
            return None
        algo = cfg["bitrot_algo"]
        if m.get("work", "encode") == "encode":
            w = work.encode_work(data, cfg["data_shards"],
                                 cfg["parity_shards"], algo)
        elif m["work"] == "decode" and q["hidden"]:
            # Every decode of a cell that hides shards rebuilds that many.
            w = work.decode_work(data, cfg["data_shards"], q["hidden"],
                                 algo)
        else:
            return None
        return 100.0 * work.least_seconds(w, q["device_kind"])["seconds"] \
            / busy
    raise RunFailure(f"metrics/{m['name']}.json: unknown kind {kind!r}")


# -- the run -----------------------------------------------------------------------

def cell_metrics(bench: dict, section: str, cell: str) -> list[dict]:
    return [m for m in bench[section]
            if cell in m.get("workloads", [cell])]


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             traffic_dir: str | None = None, keep: str | None = None,
             require_chip: bool = True,
             serve_script: str = os.path.join(HERE, "serve.py")) -> dict | None:
    """One run; the result line as a dict, or None where no TPU served
    (after every phase has run: the CPU rehearsal).  `traffic_dir` holds
    the rehearsal's small mixes; `keep` is a directory that gets the
    server's log and the profiler's trace, to read by hand; `require_chip`
    and `serve_script` are for the tests under benchmark/tests."""
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    wl, cfg = traffic.load_cell(bench, workload, traffic_dir)
    per_layer = [dict(traffic.load_metric(m["name"]), **m)
                 for m in cell_metrics(bench, "per_layer", workload)]
    root = tempfile.mkdtemp(prefix="mtpu_bench_", dir=ram_backed_dir())
    srv = clients = None
    try:
        srv = Server(root, cfg, trace, serve_script)
        clients = Clients(root, srv.port, seed, wl, cfg)
        clients.tell("pool")             # while the server boots
        srv.wait_ready()
        t_boot = time.monotonic()
        dev = srv.healthinfo_device()
        log(f"device {json.dumps(dev)}")
        need(dev["in_process"], "the serving process holds no JAX backend")
        need(dev["count"] >= cfg["chips"],
             f"{cfg['chips']} chip(s) asked for, the server sees "
             f"{dev['count']}")
        srv.configure()
        clients.gather()
        t_pool = time.monotonic()
        clients.tell("prefill")
        hide = wl.get("hide_shards", 0)
        heads = first_blocks(seed, wl) if hide else None    # meanwhile
        filled = clients.gather()
        t_prefill = time.monotonic()
        hidden = hide_shards(srv, seed, wl, cfg, heads) if hide else None
        t_hidden = time.monotonic()
        warmed = clients.all("warmup")
        if trace:
            srv.ask("trace.start", "trace.started")
        srv.client.attempts = 1         # set-up is over: nothing is resent
        m0, cpu0 = srv.metrics(), srv.cpu_seconds()
        log0 = os.path.getsize(srv.log_path)
        t0 = time.monotonic() + 0.25
        t1 = t0 + seconds
        setup_s = t0 - T_START
        log(f"setup boot_s={t_boot - T_START:.3f} "
            f"pool_wait_s={t_pool - t_boot:.3f} "
            f"prefill_s={t_prefill - t_pool:.3f} "
            f"hide_s={t_hidden - t_prefill:.3f} "
            f"warmup_s={t0 - t_hidden:.3f} setup_s={setup_s:.3f} "
            f"prefilled={sum(f['objects'] for f in filled)} "
            f"hidden={len(hidden['paths']) if hidden else 0} "
            f"warmup_requests={sum(w['requests'] for w in warmed)}")

        ran = clients.all(f"run {t0!r} {t1!r}")        # window + drain
        t_drained = time.monotonic()
        m1, cpu1 = srv.metrics(), srv.cpu_seconds()
        log1 = os.path.getsize(srv.log_path)
        if trace:
            srv.ask("trace.stop", "trace.stopped", timeout=300)
        device = srv.ask("device.req", "device.json")
        recs = clients.read_records()
        clients.stop()
        win = [r for r in recs if r["phase"] == "window"]
        need(all(r["ok"] for r in recs if r["phase"] != "window"),
             "a request failed during set-up: "
             + str(next((r for r in recs if not r["ok"]), None)))
        log(f"window seconds={seconds} drain_s={t_drained - t1:.3f} "
            f"late_s={max(r['started_late_s'] for r in ran):.4f} "
            f"setup_reconnects="
            f"{srv.client.reconnects + sum(r['setup_reconnects'] for r in ran)} "
            f"compiled_in_window="
            f"{json.dumps(srv.compiles_between(log0, log1))} "
            f"gbps_per_5s={timeline(win, t0, t1)} "
            f"ops={json.dumps(op_table(win))}")

        t_check = time.monotonic()
        correct, compared = check_correct(
            srv, seed, wl, cfg, win, [r["live"] for r in ran],
            lambda n: (counter(m1, n) or 0) - (counter(m0, n) or 0), hidden)
        log(f"check seconds={time.monotonic() - t_check:.3f}")
        rc = srv.stop()
        need(rc == 0, f"the server exited {rc} on SIGTERM:\n{srv.log_tail()}")

        q = {"metrics0": m0, "metrics1": m1, "device_kind": device["kind"],
             "client": client_quantities(win, t0, t_drained),
             "proc": {"cpu_s": cpu1 - cpu0}, "trace": None, "hidden": hide}
        out_device = {"platform": device["platform"], "kind": device["kind"],
                      "count": device["count"],
                      "memory_peak_bytes": device["memory_peak_bytes"]}
        result = {"correct": correct, "attempted": len(win),
                  "failed": sum(not r["ok"] for r in win)}
        if trace:
            red = subprocess.run(
                [sys.executable, os.path.join(HERE, "trace_reduce.py"),
                 os.path.join(srv.ctl, "trace")],
                env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=CHECKOUT,
                capture_output=True, text=True, timeout=300)
            need(red.returncode == 0, f"trace_reduce failed:\n{red.stderr}")
            q["trace"] = json.loads(red.stdout.splitlines()[-1])
            log(f"trace {red.stdout.splitlines()[-1]}")
            values = {m["name"]: read_metric(m, q, cfg) for m in per_layer}
            units = {m["name"]: m["unit"] for m in per_layer}
            out_device["busy_s"] = q["trace"]["busy_s"]
            out_device["window_s"] = q["client"]["window_s"]
            result["breakdown"] = {"device_ops": q["trace"]["ops"],
                                   "idle_gaps": q["trace"]["gaps"]}
        else:
            values = dict(end_to_end(win, t0, t1), setup_s=setup_s)
            wanted = cell_metrics(bench, "end_to_end", workload)
            units = {m["name"]: m["unit"] for m in wanted}
            need(set(units) <= set(values),
                 f"the cell reports no {sorted(set(units) - set(values))}")
            values = {n: values[n] for n in units}
        result["metrics"] = {n: {"value": v, "unit": units[n]}
                             for n, v in values.items() if v is not None}
        result["device"] = out_device
        result["compared"] = compared
        if device["platform"] != "tpu":
            log(f"no chip: every phase ran, but the server computes on "
                f"platform={device['platform']!r}; result withheld: "
                f"{json.dumps(result)[:1500]}")
            return result if not require_chip else None
        return result
    finally:
        if clients is not None:
            clients.stop()
        if srv is not None:
            srv.kill()
            if keep:
                os.makedirs(keep, exist_ok=True)
                shutil.copy(srv.log_path, keep)
                for path in glob.glob(os.path.join(
                        srv.ctl, "trace", "plugins", "profile", "*", "*.pb")):
                    shutil.copy(path, keep)
        shutil.rmtree(root, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--traffic-dir", default=None,
                    help="read the cell's traffic mix from this directory "
                         "(benchmark/tests/traffic: the rehearsal's sizes)")
    ap.add_argument("--keep", default=None,
                    help="copy the server's log and the profiler's trace "
                         "into this directory before cleaning up")
    args = ap.parse_args()
    result = run_cell(args.workload, abs(args.seed), args.seconds,
                      bool(args.trace), traffic_dir=args.traffic_dir,
                      keep=args.keep)
    need("jax" not in sys.modules, "the parent imported jax")
    if result is None:
        return 1
    for name, row in result["compared"].items():
        log(f"compared {name}: {json.dumps(row)}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except RunFailure as e:
        log(f"benchmark/run.py: FAILED: {e}")
        sys.exit(1)
