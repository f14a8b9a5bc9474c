#!/usr/bin/env python3
"""Live-tail a server's request trace stream, or dump top-API stats.

Streams ``POST /minio/admin/v3/trace`` (chunked NDJSON of span trees)
and pretty-prints each request as an indented stage tree, newest last:

    $ python tools/trace_dump.py --endpoint http://127.0.0.1:9000 \
          --access-key minioadmin --secret-key minioadmin --duration 30
    06:25:51.312 api.PutObject  200  /bkt/obj  44.1ms
      engine.etag                        25.31ms
      engine.encode                       5.84ms
      engine.stage                       10.87ms
        drive.write                       9.02ms

``--json`` emits the raw NDJSON records instead.  ``--top`` skips the
stream and prints ``GET /minio/admin/v3/top/apis`` aggregates (count,
errors, avg/p50/p90/p99, hottest stages per API with how much of each
stage's self time its thread ran and waited).  ``--ring`` skips the
stream too and prints what the server's retention ring holds
(``MTPU_TRACE_RING``; ``GET /minio/admin/v3/trace?trees=1``): request
roots and the lanes' ``lane.dispatch`` roots, oldest first, each stage
with its wall time and, where the span read its thread's CPU clock,
that time and the split of its own time (with that of the spans below
it that read none) into ran and waited.

Filters mirror `mc admin trace`: ``--err`` (errors only), ``--path``
(request-path prefix), ``--min-duration-ms``.  Credentials fall back to
MTPU_ACCESS_KEY / MTPU_SECRET_KEY.
"""

import argparse
import json
import os
import sys
import urllib.parse

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from minio_tpu.server.client import S3Client  # noqa: E402
from minio_tpu.server.sigv4 import sign_request  # noqa: E402


def stream_trace(cli: S3Client, query: dict):
    """POST v3/trace and yield NDJSON lines AS THEY ARRIVE (the generic
    S3Client.request buffers the whole body, which would defeat a live
    tail)."""
    path = "/minio/admin/v3/trace"
    q = {k: [v] for k, v in query.items()}
    headers = {"Host": f"{cli.host}:{cli.port}"}
    headers.update(sign_request(cli.creds, "POST", path, q, headers,
                                b""))
    qs = urllib.parse.urlencode(query)
    conn = cli._connect(max(120.0, float(query["duration"]) + 60))
    try:
        conn.request("POST", f"{path}?{qs}", headers=headers)
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(
                f"trace failed: HTTP {resp.status}: "
                f"{resp.read()[:200]!r}")
        buf = b""
        while True:
            piece = resp.read1(65536)
            if not piece:
                break
            buf += piece
            while b"\n" in buf:
                line, buf = buf.split(b"\n", 1)
                if line.strip():
                    yield line
        if buf.strip():
            yield buf
    finally:
        conn.close()


def _fmt_time(ts: float) -> str:
    import datetime
    return datetime.datetime.fromtimestamp(ts).strftime("%H:%M:%S.%f")[:-3]


def print_rec(rec: dict) -> None:
    tags = rec.get("tags", {})
    status = tags.get("status", "?")
    mark = " ERR" if rec.get("error") else ""
    print(f'{_fmt_time(rec.get("time", 0))} {rec["name"]:<20} {status}  '
          f'{tags.get("path", "")}  {rec["dur_ms"]:.1f}ms{mark}')
    stack = [(c, 1) for c in reversed(rec.get("spans", []))]
    while stack:
        sp, depth = stack.pop()
        pad = "  " * depth
        cpu = (f'  cpu {sp["cpu_ms"]:>9.2f}ms' if "cpu_ms" in sp else "")
        if "self_wait_ms" in sp:
            # Its own time, with that of the spans it keeps the clock
            # for; one span's wait is floored at 0.
            cpu += (f'  own {sp["clocked_self_ms"]:.2f} = ran '
                    f'{sp["self_cpu_ms"]:.2f} + waited '
                    f'{sp["self_wait_ms"]:.2f}')
        print(f'{pad}{sp["name"]:<{34 - 2 * depth}} '
              f'{sp["dur_ms"]:>9.2f}ms{cpu}')
        stack.extend((c, depth + 1)
                     for c in reversed(sp.get("spans", [])))


def dump_top(cli: S3Client) -> int:
    st, _, body = cli.request("GET", "/minio/admin/v3/top/apis")
    if st != 200:
        print(f"top/apis failed: HTTP {st}: {body[:200]!r}",
              file=sys.stderr)
        return 1
    snap = json.loads(body)
    apis = snap.get("apis", {})
    if not apis:
        print("no traced requests yet (tracing is demand-driven: "
              "start a trace stream or set MTPU_TRACE_RING)")
        return 0
    hdr = (f'{"api":<24} {"count":>6} {"errs":>5} {"avg_ms":>9} '
           f'{"p50_ms":>9} {"p90_ms":>9} {"p99_ms":>9}')
    print(hdr)
    print("-" * len(hdr))
    for api, a in sorted(apis.items(),
                         key=lambda kv: -kv[1]["count"]):
        print(f'{api:<24} {a["count"]:>6} {a["errors"]:>5} '
              f'{a["avg_ms"]:>9.2f} {a["p50_ms"]:>9.2f} '
              f'{a["p90_ms"]:>9.2f} {a["p99_ms"]:>9.2f}')
        top = sorted(a.get("stages", {}).items(),
                     key=lambda kv: -kv[1]["total_ms"])[:5]
        for name, st_ in top:
            print(f'    {name:<28} x{st_["count"]:<5} '
                  f'{st_["total_ms"]:>9.2f}ms total  self '
                  f'{st_["self_ms"]:.2f} = ran '
                  f'{st_.get("self_cpu_ms", 0.0):.2f} + waited '
                  f'{st_.get("self_wait_ms", 0.0):.2f}')
    return 0


def dump_ring(cli: S3Client, raw: bool) -> int:
    st, _, body = cli.request("GET", "/minio/admin/v3/trace",
                              query={"trees": "1"})
    if st != 200:
        print(f"trace?trees=1 failed: HTTP {st}: {body[:200]!r}",
              file=sys.stderr)
        return 1
    recs = json.loads(body)["traces"]
    for rec in recs:
        if raw:
            print(json.dumps(rec))
        else:
            print_rec(rec)
    if not raw:
        print(f"-- {len(recs)} root(s) in the ring --")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="stream request span traces from a minio_tpu server")
    ap.add_argument("--endpoint", default="http://127.0.0.1:9000")
    ap.add_argument("--access-key",
                    default=os.environ.get("MTPU_ACCESS_KEY", ""))
    ap.add_argument("--secret-key",
                    default=os.environ.get("MTPU_SECRET_KEY", ""))
    ap.add_argument("--duration", type=float, default=10.0,
                    help="seconds to stream (server closes after)")
    ap.add_argument("--err", action="store_true",
                    help="only failed requests")
    ap.add_argument("--path", default="",
                    help="request-path prefix filter, e.g. /bucket")
    ap.add_argument("--min-duration-ms", type=float, default=0.0)
    ap.add_argument("--json", action="store_true",
                    help="raw NDJSON records instead of trees")
    ap.add_argument("--top", action="store_true",
                    help="print top/apis aggregates and exit")
    ap.add_argument("--ring", action="store_true",
                    help="print the retention ring's trees and exit")
    args = ap.parse_args(argv)
    if not args.access_key or not args.secret_key:
        ap.error("--access-key/--secret-key (or MTPU_ACCESS_KEY/"
                 "MTPU_SECRET_KEY) required")

    cli = S3Client(args.endpoint, args.access_key, args.secret_key)
    if args.top:
        return dump_top(cli)
    if args.ring:
        return dump_ring(cli, args.json)

    query = {"duration": str(args.duration)}
    if args.err:
        query["err"] = "true"
    if args.path:
        query["path"] = args.path
    if args.min_duration_ms:
        query["min-duration-ms"] = str(args.min_duration_ms)
    n = 0
    try:
        for line in stream_trace(cli, query):
            if args.json:
                sys.stdout.buffer.write(line + b"\n")
                sys.stdout.buffer.flush()
            else:
                print_rec(json.loads(line))
                sys.stdout.flush()
            n += 1
    except RuntimeError as e:
        print(e, file=sys.stderr)
        return 1
    except KeyboardInterrupt:
        pass
    if not args.json:
        print(f"-- {n} request(s) in {args.duration:g}s --")
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:     # `trace_dump.py | head` is fine
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
