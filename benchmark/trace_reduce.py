"""From a profiler trace (`*.xplane.pb`) to device busy time and the
operations that took it.  Runs in a process of its own, after the server has
exited: `jax.profiler.ProfileData` needs `import jax`, and the parent of a run
never imports it.

    python benchmark/trace_reduce.py <trace dir>            one JSON line
    python benchmark/trace_reduce.py <trace dir> --dump     planes and lines,
                                                            to read by hand

What the TPU's planes look like (read by hand on a v5e, PR 26): one plane per
chip, `/device:TPU:<n>`, with a line `XLA Ops` (one event per executed HLO
operation, fusions and custom calls under their HLO names), a line
`XLA Modules` (one event per executed program, named `jit_<function>(<id>)`)
and a line `Steps`.  Busy time is the union of the `XLA Ops` events: an
interval in which any operation ran on the chip.  Where a plane has no
`XLA Ops` line the union is taken over `XLA Modules`.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops", "XLA Modules")


def union_ns(intervals: list[tuple[float, float]]) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_pct(busy_s: float, window_s: float) -> float:
    return 100.0 * (1.0 - busy_s / window_s)


def short(hlo: str) -> str:
    """`%name = type[shape]{layout} opcode(operands...)` -> `name opcode
    type[shape]`: an operation's whole HLO text is its name in the trace."""
    lhs, sep, rhs = hlo.partition(" = ")
    if not sep:
        return hlo[:80]
    rhs = re.sub(r"\{[^{}]*\}", "", rhs)            # layouts
    shape = re.match(r"\(?\s*([a-z]+\d*\[[\d,]*\])", rhs)
    if rhs.startswith("("):                         # a tuple type
        rhs = rhs[rhs.index(") ") + 2:] if ") " in rhs else rhs
    else:
        rhs = rhs.partition(" ")[2]
    opcode = rhs.split("(")[0].strip()
    return f"{lhs.lstrip('%')} {opcode} {shape.group(1) if shape else ''}"[:80]


GAP_EDGES_S = (0.001, 0.01, 0.1, 1.0)


def gap_bucket(seconds: float) -> str:
    lo = 0.0
    for hi in GAP_EDGES_S:
        if seconds < hi:
            return f"idle between programs, gaps of {lo:g}-{hi:g} s"
        lo = hi
    return f"idle between programs, gaps over {lo:g} s"


def top(named: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in
            sorted(named.items(), key=lambda kv: -kv[1])[:n]]


def find_xplane(trace_dir: str) -> str:
    """The one trace under the profiler's log directory (or, for a copy
    kept by `run.py --keep`, directly in it)."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        + glob.glob(os.path.join(trace_dir, "*.xplane.pb")))
    if len(found) != 1:
        raise SystemExit(f"trace_reduce: {len(found)} xplane files under "
                         f"{trace_dir}, want 1")
    return found[0]


def reduce(path: str) -> dict:
    """{"chips": n, "busy_s": mean over chips of the union of op intervals,
    "ops": [[name, seconds summed over chips], ...], "modules": the same by
    executed program, "gaps": idle seconds between executed programs, by the
    length of the gap (what the host did in them the trace cannot say: the
    program puts no host spans on the profiler's clock)}."""
    from jax.profiler import ProfileData
    busy, ops, modules, gaps = [], {}, {}, {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        op_line = next((lines[n] for n in OP_LINES if n in lines), None)
        spans = []
        for ev in (op_line.events if op_line is not None else ()):
            spans.append((ev.start_ns, ev.start_ns + ev.duration_ns))
            name = short(ev.name)
            ops[name] = ops.get(name, 0.0) + ev.duration_ns / 1e9
        busy.append(union_ns(spans) / 1e9)
        prev = None
        for ev in sorted(lines["XLA Modules"].events if "XLA Modules" in lines
                         else (), key=lambda e: e.start_ns):
            name = ev.name.split("(")[0]
            modules[name] = modules.get(name, 0.0) + ev.duration_ns / 1e9
            if prev is not None and ev.start_ns > prev:
                gap = gap_bucket((ev.start_ns - prev) / 1e9)
                gaps[gap] = gaps.get(gap, 0.0) + (ev.start_ns - prev) / 1e9
            prev = max(prev or 0, ev.start_ns + ev.duration_ns)
    return {"chips": len(busy),
            "busy_s": sum(busy) / len(busy) if busy else None,
            "ops": top(ops), "modules": top(modules), "gaps": top(gaps)}


def dump(path: str) -> None:
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for ln in plane.lines:
            evs = list(ln.events)
            print(f"  LINE {ln.name!r}: {len(evs)} events")
            for ev in evs[:6]:
                print(f"    {ev.name[:100]!r} start_ns={ev.start_ns} "
                      f"dur_ns={ev.duration_ns}")


def main() -> int:
    path = find_xplane(sys.argv[1])
    if "--dump" in sys.argv[2:]:
        dump(path)
    else:
        print(json.dumps(reduce(path)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
