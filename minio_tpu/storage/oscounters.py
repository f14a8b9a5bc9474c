"""Per-OS-call counters/timings for the storage layer.

The cmd/os-instrumented.go role: every syscall class the drive layer
issues is counted and timed, so `disk_info()`/admin metrics can show
where drive time goes (complements the per-API EWMAs in
storage/health_wrap.py, the xlStorageDiskIDCheck role)."""

from __future__ import annotations

import threading
import time
from collections import defaultdict
from contextlib import contextmanager

from ..observe.span import span as _span

class Counters:
    """One instance per drive, so per-drive numbers actually attribute
    to the drive (a process-wide singleton would report identical
    aggregates under every drive and overcount N x when summed).

    `drive` labels the owning drive; inside a traced request every
    timed op is also a per-drive I/O span, "storage.<stage>" (the
    drive call: append, create, meta_write) or "storage.<op>" where no
    stage is named — one contextvar read when tracing is off."""

    def __init__(self, drive: str = ""):
        self._mu = threading.Lock()
        self._counts: dict[str, int] = defaultdict(int)
        self._seconds: dict[str, float] = defaultdict(float)
        self._drive = drive

    @contextmanager
    def timed(self, op: str, stage: str | None = None):
        with _span("storage." + (stage or op)) as sp:
            sp.tag(drive=self._drive)
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.add(op, time.perf_counter() - t0)

    def add(self, op: str, seconds: float) -> None:
        """Count one `op` its caller timed: a drive call made with
        others in one native call (drive.read_rows), whose span is that
        call's."""
        with self._mu:
            self._counts[op] += 1
            self._seconds[op] += seconds

    def snapshot(self) -> dict:
        with self._mu:
            return {op: {"count": self._counts[op],
                         "total_ms": round(self._seconds[op] * 1e3, 3)}
                    for op in sorted(self._counts)}

    def reset(self) -> None:
        with self._mu:
            self._counts.clear()
            self._seconds.clear()
