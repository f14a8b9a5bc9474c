"""One closed-loop client of a benchmark run: one process, one connection.

    python benchmark/loadgen.py <spec.json>

The spec (written by `run.py`) names the server, the bucket, the seed, this
client's index, the workload's parameters and where the records go.  The
parent speaks over stdin, one command a line, and reads one JSON answer a
line from stdout:

    pool                 make the seeded pool buffers
    prefill              PUT this client's prefilled objects
    warmup               one operation of every kind in the mix
    run <t0> <t1>        from time.monotonic() == t0, send the next operation
                         as soon as the last one is answered, until t1; then
                         write one JSON record per request to the spec's
                         `records` file
    quit

Imports the benchmark's own client and traffic rules, numpy and the standard
library: nothing of the program, no JAX.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import traffic  # noqa: E402
from s3client import S3Client, S3Error  # noqa: E402


class Client:
    def __init__(self, spec: dict):
        self.spec = spec
        self.wl = spec["workload"]
        self.idx = spec["client"]
        self.bucket = spec["bucket"]
        self.headers = spec["put_headers"]
        self.cli = S3Client(spec["host"], spec["port"])
        self.bodies = traffic.Bodies(spec["seed"], self.idx, self.wl)
        self.ops = traffic.op_blocks(spec["seed"], self.idx, self.wl["mix"])
        self.pick = traffic.picker(spec["seed"], self.idx)
        self.live: list[tuple[str, int]] = []     # (key, generation)
        # Where the mix says so, every GET's body lands in this one buffer
        # and is compared there; else in fresh bytes a GET.
        self.got = bytearray(self.wl["object_bytes"]) \
            if self.wl.get("reuse_get_buffer") else None
        self.nput = 0                             # PUTs begun, ever
        self.records: list[dict] = []
        self.phase = "setup"
        self.deadline: float | None = None        # the window's end, in it

    # -- one request -----------------------------------------------------------

    def _timed(self, op: str, key: str, nbytes: int, call, **more):
        rec = {"client": self.idx, "phase": self.phase, "op": op, "key": key,
               "bytes": nbytes, **more}
        rec["t0"] = time.monotonic()
        try:
            out = call()
            rec["ok"] = True
        except (S3Error, OSError) as e:
            out, rec["ok"], rec["error"] = None, False, repr(e)[:200]
        rec["t1"] = time.monotonic()
        self.records.append(rec)
        return out

    def put(self, key: str, gen: int) -> bool:
        """One object: a single PUT, or create + parts + complete."""
        size = self.bodies.size
        parts = traffic.parts_of(self.wl)
        if parts == [0]:
            return self._timed(
                "PUT", key, size, lambda: self.cli.put_object(
                    self.bucket, key, self.bodies.chunks(key, 0, gen), size,
                    headers=self.headers), gen=gen) is not None
        uid = self._timed("CREATE", key, 0, lambda: self.cli.create_multipart(
            self.bucket, key, headers=self.headers), gen=gen)
        if uid is None:
            return False
        etags = []
        for n in parts:
            if self.deadline is not None and time.monotonic() >= self.deadline:
                return False          # the window closed inside this object
            etag = self._timed(
                "PART", key, size, lambda n=n: self.cli.put_object(
                    self.bucket, key, self.bodies.chunks(key, n, gen), size,
                    query={"partNumber": str(n), "uploadId": uid}),
                gen=gen, part=n)
            if etag is None:
                return False
            etags.append((n, etag))
        if self.deadline is not None and time.monotonic() >= self.deadline:
            return False
        return self._timed(
            "COMPLETE", key, 0, lambda: self.cli.complete_multipart(
                self.bucket, key, uid, etags) or True, gen=gen) is not None

    def get(self, key: str, gen: int) -> None:
        got = self._timed("GET", key, self.wl["object_bytes"],
                          lambda: self.cli.get_object(self.bucket, key,
                                                      into=self.got),
                          gen=gen)
        if got is not None:
            # Compared after the clock stopped: the request's time is the
            # server's, the comparison is this client's.
            self.records[-1]["match"] = self.bodies.matches(
                got, key, gen, self.wl)

    def step(self, op: str) -> None:
        ring = self.wl["put_key_ring"]
        if op == "PUT":
            n, self.nput = self.nput, self.nput + 1
            if ring:
                key, gen = traffic.ring_key(self.idx, n % ring), n // ring
                if self.put(key, gen):
                    self.live = [kg for kg in self.live if kg[0] != key]
                    self.live.append((key, gen))
            else:
                key = traffic.new_key(self.idx, n)
                if self.put(key, 0):
                    self.live.append((key, 0))
            return
        if not self.live:
            raise RuntimeError(f"client {self.idx}: {op} with no live object: "
                               f"the mix deletes more than it keeps")
        i = self.pick(len(self.live))
        key, gen = self.live[i]
        if op == "GET":
            self.get(key, gen)
        elif op == "STAT":
            self._timed("STAT", key, 0,
                        lambda: self.cli.head_object(self.bucket, key))
        elif op == "DELETE":
            self.live.pop(i)
            self._timed("DELETE", key, 0,
                        lambda: self.cli.delete_object(self.bucket, key) or 1)

    # -- phases ----------------------------------------------------------------

    def prefill(self) -> dict:
        self.phase = "prefill"
        for _ in range(self.wl["prefill_per_client"]):
            self.step("PUT")
        return {"objects": len(self.live)}

    def warmup(self) -> dict:
        """One operation of every kind the mix has, in an order that leaves
        the live set as large as it was (PUT first, DELETE last)."""
        self.phase = "warmup"
        for op in traffic.OPS:
            if self.wl["mix"].get(op):
                self.step(op)
        return {"requests": sum(r["phase"] == "warmup" for r in self.records)}

    def run(self, t0: float, t1: float) -> dict:
        self.phase = "window"
        self.deadline = t1
        self.cli.attempts = 1         # set-up is over: nothing is resent
        t0 += traffic.start_offset(self.spec["seed"], self.idx, self.wl)
        time.sleep(max(0.0, t0 - time.monotonic()))
        late = time.monotonic() - t0
        while time.monotonic() < t1:
            self.step(next(self.ops))
        self.deadline = None
        with open(self.spec["records"], "w") as f:
            for rec in self.records:
                f.write(json.dumps(rec) + "\n")
        return {"started_late_s": late, "live": self.live,
                "setup_reconnects": self.cli.reconnects}


def main() -> int:
    with open(sys.argv[1]) as f:
        client = Client(json.load(f))
    for line in sys.stdin:
        cmd, *args = line.split()
        if cmd == "quit":
            break
        if cmd == "pool":
            client.bodies.fill()
            out = {"buffers": client.bodies.npool}
        elif cmd == "prefill":
            out = client.prefill()
        elif cmd == "warmup":
            out = client.warmup()
        elif cmd == "run":
            out = client.run(float(args[0]), float(args[1]))
        else:
            raise ValueError(f"unknown command {line!r}")
        print(json.dumps({"done": cmd, **out}), flush=True)
    client.cli.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
