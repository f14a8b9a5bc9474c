"""What the host was doing while the chip sat idle: the idle gaps between
executed programs on the device, split over the program's own spans that were
open on its host threads at the time.

    python benchmark/host_gaps.py <trace dir>       one JSON line

The trace is one kept by `run.py --trace 1 --keep <dir>`.  The program opens a
`jax.profiler.TraceAnnotation` for every real span (`minio_tpu/observe/
span.py`), and `serve.py` runs the profiler with `host_tracer_level = 1`, so
each span is an event on its host thread's line of the `/host:CPU` plane,
stamped by the profiler itself: the same clock as the `XLA Modules` events on
`/device:TPU:*`.  A span event is told from the runtime's own host events by
its name (`SPAN_PREFIXES`).

For every gap between two programs on a device, each instant goes in equal
parts to the host threads that have a span open then, under the name of the
deepest span open on that thread (the one begun last); an instant with no span
open on any thread goes to `no span open`.  The seconds over all names add up
to the idle seconds between programs.  `run.py` does not call this yet: a
later `benchmark` issue wires it into `breakdown.idle_gaps`.
"""

from __future__ import annotations

import bisect
import heapq
import json
import sys

import trace_reduce

HOST_PLANE = "/host:CPU"
NO_SPAN = "no span open"
LONG_GAP_S = 0.010
#: Names the program gives its spans (request roots, then the stage prefixes
#: of observe/span.py's layer table, host kernels, heal stages).
SPAN_PREFIXES = ("api.", "admin.", "internal.", "http.", "engine.", "mp.",
                 "storage.", "host.hash_batch", "native.", "coalesce.",
                 "ipc.", "metalane.", "lane.", "device.", "heal.")

Interval = tuple[float, float]
Named = tuple[float, float, str]


def deepest(events: list[Named]) -> list[Named]:
    """One thread's (start, end, name) events, nested or overlapping, as
    segments that do not overlap, each under the name of the event begun
    last among those open in it (of two begun together, the shorter)."""
    evs = sorted(events)
    cuts = sorted({t for s, e, _ in evs for t in (s, e)})
    out: list[Named] = []
    open_: list[tuple[float, float, str]] = []   # heap: (-start, end, name)
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(evs) and evs[i][0] <= a:
            heapq.heappush(open_, (-evs[i][0], evs[i][1], evs[i][2]))
            i += 1
        while open_ and open_[0][1] <= a:
            heapq.heappop(open_)
        if not open_:
            continue
        name = open_[0][2]
        if out and out[-1][1] == a and out[-1][2] == name:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def attribute(gaps: list[Interval],
              flat: dict[str, list[Named]]) -> dict[str, float]:
    """Seconds of `gaps` by the name of the deepest span open on a host
    thread (equal parts where several threads have one open; `NO_SPAN`
    where none has).  `flat` holds each thread's `deepest` segments."""
    starts = {t: [s for s, _, _ in segs] for t, segs in flat.items()}
    out: dict[str, float] = {}
    for g0, g1 in gaps:
        inside: list[Named] = []
        for t, segs in flat.items():
            i = max(0, bisect.bisect_right(starts[t], g0) - 1)
            while i < len(segs) and segs[i][0] < g1:
                s, e, name = segs[i]
                if e > g0:
                    inside.append((max(s, g0), min(e, g1), name))
                i += 1
        cuts = sorted({g0, g1, *(s for s, _, _ in inside),
                       *(e for _, e, _ in inside)})
        for a, b in zip(cuts, cuts[1:]):
            names = [n for s, e, n in inside if s <= a and e >= b]
            for n in names or [NO_SPAN]:
                out[n] = out.get(n, 0.0) + (b - a) / max(1, len(names))
    return out


def program_gaps(events: list[Interval]) -> list[Interval]:
    """The idle intervals between executed programs on one device."""
    gaps, end = [], None
    for s, e in sorted(events):
        if end is not None and s > end:
            gaps.append((end, s))
        end = e if end is None else max(end, e)
    return gaps


def read(path: str) -> tuple[list[Interval], dict[str, list[Named]]]:
    """(idle gaps between `XLA Modules` events over all chips, the program's
    span events per host thread), in seconds on the profiler's clock."""
    from jax.profiler import ProfileData
    gaps: list[Interval] = []
    threads: dict[str, list[Named]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            for ln in plane.lines:
                if ln.name == "XLA Modules":
                    gaps += program_gaps(
                        [(ev.start_ns / 1e9,
                          (ev.start_ns + ev.duration_ns) / 1e9)
                         for ev in ln.events])
        elif plane.name == HOST_PLANE:
            for i, ln in enumerate(plane.lines):
                evs = [(ev.start_ns / 1e9,
                        (ev.start_ns + ev.duration_ns) / 1e9, ev.name)
                       for ev in ln.events
                       if ev.name.startswith(SPAN_PREFIXES)]
                if evs:
                    threads[f"{i}:{ln.name}"] = evs
    return gaps, threads


def reduce(path: str) -> dict:
    gaps, threads = read(path)
    flat = {t: deepest(evs) for t, evs in threads.items()}
    by_name = attribute(gaps, flat)
    long_by_name = attribute(
        [g for g in gaps if g[1] - g[0] > LONG_GAP_S], flat)
    long_s = sum(long_by_name.values())
    return {"idle_s": sum(by_name.values()),
            "host_threads_with_spans": len(threads),
            "span_events": sum(len(v) for v in threads.values()),
            "gaps": trace_reduce.top(by_name),
            "idle_over_10ms_s": long_s,
            "named_share_over_10ms":
                (1.0 - long_by_name.get(NO_SPAN, 0.0) / long_s
                 if long_s else None),
            "gaps_over_10ms": trace_reduce.top(long_by_name)}


def main() -> int:
    print(json.dumps(reduce(trace_reduce.find_xplane(sys.argv[1]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
