"""The serving process of a benchmark run: the program's normal entry point
with a profiler around it.  Not a second server.

    python benchmark/serve.py <control dir> <trace 0|1> <server argv...>

`minio_tpu.server.__main__.main(argv)` runs on the main thread, so its SIGTERM
handling and exit code are the program's own.  This is the only process of a
run that imports JAX, hence the only one that can trace the chip or read its
memory.  A small thread answers the parent through files in the control
directory (requests are empty files the parent creates; answers are written
under a temporary name and renamed):

    trace.start  ->  jax.profiler.start_trace(<control dir>/trace), Python
                     tracer off so the file stays small; answers trace.started
    trace.stop   ->  stop_trace(); answers trace.stopped {"start", "stop"}
                     (time.monotonic() at both edges)
    device.req   ->  answers device.json: platform, kind, count and the peak
                     bytes in use on the fullest device, as JAX reports them
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _answer(ctl: str, name: str, doc: dict) -> None:
    tmp = os.path.join(ctl, name + ".tmp")
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, os.path.join(ctl, name))


def _take(ctl: str, name: str) -> bool:
    """Whether the parent has made request `name`; consumes it."""
    try:
        os.unlink(os.path.join(ctl, name))
    except FileNotFoundError:
        return False
    return True


def _device_doc() -> dict:
    import jax
    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max((p for p in peaks if p is not None),
                                     default=None)}


def control(ctl: str) -> None:
    started = None
    while True:
        if _take(ctl, "device.req"):
            _answer(ctl, "device.json", _device_doc())
        if started is None and _take(ctl, "trace.start"):
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(os.path.join(ctl, "trace"),
                                     profiler_options=opts)
            started = time.monotonic()
            _answer(ctl, "trace.started", {"start": started})
        if started is not None and _take(ctl, "trace.stop"):
            import jax
            stopped = time.monotonic()
            jax.profiler.stop_trace()
            _answer(ctl, "trace.stopped", {"start": started, "stop": stopped})
            started = None
        time.sleep(0.02)


def main() -> int:
    ctl, trace, argv = sys.argv[1], sys.argv[2] == "1", sys.argv[3:]
    sys.path.insert(0, CHECKOUT)
    if trace:
        # The program's span aggregates count only while its ring is on.
        os.environ.setdefault("MTPU_TRACE_RING", "256")
    threading.Thread(target=control, args=(ctl,), daemon=True,
                     name="bench-control").start()
    from minio_tpu.server.__main__ import main as server_main
    return server_main(argv)


if __name__ == "__main__":
    sys.exit(main())
