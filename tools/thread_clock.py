#!/usr/bin/env python3
"""What `time.thread_time()` means on this host, in a few readings.

The trace plane (minio_tpu/observe/span.py) splits a span's self time
into the part its thread ran and the part it waited by the thread's CPU
clock.  That split reads as intended only where the clock (a) stands
still while the thread sleeps, (b) is or is not charged the page faults
of a first touch (which decides how a copy into fresh pages reads), and
(c) is shared out between threads that take turns under the GIL.  A
sandboxed kernel need not answer as Linux does, so run this once on the
machine that serves (PERF.md section 3 holds the chip host's answers):

    python tools/thread_clock.py        one JSON line

No JAX, no server; about a second.
"""

import json
import sys
import threading
import time

import numpy as np

MIB = 1 << 20


def on_thread(fn) -> dict:
    """`fn()` on a thread of its own: wall and thread-CPU ms around it."""
    out = {}

    def run():
        c0, t0 = time.thread_time(), time.monotonic()
        fn()
        out["wall_ms"] = round((time.monotonic() - t0) * 1e3, 3)
        out["cpu_ms"] = round((time.thread_time() - c0) * 1e3, 3)

    t = threading.Thread(target=run)
    t.start()
    t.join()
    return out


def touch_twice() -> dict:
    """A byte a page of 64 MiB of fresh `np.empty`, then once more."""
    a = np.empty(64 * MIB, dtype=np.uint8)

    def touch():
        a[::4096] = 1

    return {"first": on_thread(touch), "second": on_thread(touch)}


def spin() -> None:
    end = time.monotonic() + 0.2
    while time.monotonic() < end:
        sum(range(500))


def read_cost_us(clock, n: int = 200_000) -> float:
    t0 = time.perf_counter()
    for _ in range(n):
        clock()
    return round((time.perf_counter() - t0) / n * 1e6, 4)


def main() -> int:
    spinners = [{}, {}]

    def spin_into(slot):
        spinners[slot].update(on_thread(spin))

    pair = [threading.Thread(target=spin_into, args=(i,)) for i in (0, 1)]
    p0 = time.process_time()
    for t in pair:
        t.start()
    for t in pair:
        t.join()
    both_cpu_ms = round((time.process_time() - p0) * 1e3, 3)
    print(json.dumps({
        "sleep_100ms": on_thread(lambda: time.sleep(0.1)),
        "touch_64mib": touch_twice(),
        "two_spinners_200ms": spinners,
        "two_spinners_process_cpu_ms": both_cpu_ms,
        "thread_time_read_us": read_cost_us(time.thread_time),
        "monotonic_read_us": read_cost_us(time.monotonic),
        "get_ident_read_us": read_cost_us(threading.get_ident),
        "thread_time_clock": str(time.get_clock_info("thread_time")),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
