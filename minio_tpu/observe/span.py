"""Request-scoped span trees: the madmin trace / `mc admin top apis`
observability plane (cf. cmd/admin-handlers.go TraceHandler and
internal/pubsub usage in the reference).

A request opens ONE root span (``TRACER.root("api.PutObject", ...)``);
code anywhere below it on the same logical call chain opens nested
stage spans with the module-level ``span("engine.encode")`` helper, or
attaches pre-measured timings with ``record(name, seconds)`` /
``bracket(name, start, end)``.  Span placement rides contextvars,
so the tree needs no plumbing through call signatures; fan-out code
that jumps threads wraps the worker callable in ``wrap_ctx`` to carry
the current span across.  A coalescer lane thread serves many requests
at once, so it opens a root of its own per dispatch (``lane.dispatch``)
that names the requests it serves (``members``).

One clock: every span starts and ends on ``time.monotonic()``, the
clock the lane timers (ops/coalesce.py) and the benchmark's traced
window use, so a record places each span on one timeline: ``start_ms``
is the offset from the root's start, ``self_ms`` the span's duration
minus the union of its children's intervals (children that ran side by
side in pool threads are not subtracted twice).  ``layer_of`` maps a
stage name to the layer of the system it belongs to; the exporter sums
self time per layer, which is what says where a request's time went.

Ran or waited: beside the wall clock a span reads its thread's CPU
clock (``time.thread_time()``) where it is entered and left, so a
record says how much of the span its thread ran: ``cpu_ms`` (enter to
exit, the stretch a suspended span sat out not counted).  The read is a
system call (6 us under a sandboxed kernel, PERF.md section 3), so a
span makes it only where a reader needs it: a root, a span entered on
another thread than its parent's (a ``wrap_ctx`` pool hop), a span
whose layer differs from its parent's, and the stages of ``CPU_STAGES``.
Every other span (same thread, same layer: ``record()`` / ``bracket()``
stages too) reads no clock and belongs to the *clock group* of the
nearest span above it that does: ``self_cpu_ms`` = that span's
``cpu_ms`` minus the ``cpu_ms`` of the clocked spans below the group on
the same thread (a pool thread's CPU is its own), and
``clocked_self_ms`` = the self time it is the CPU of, the group's.
``self_wait_ms`` = ``clocked_self_ms`` - ``self_cpu_ms`` floored at 0
(the thread slept, or queued for the GIL or a lock) is one span's, for
a reader of trees; the exporter floors the *sums* a stage instead, so a
coarse clock's lumps cancel.  A reading that does not exist is absent,
never guessed: a span left on another thread than it was entered on has
no ``cpu_ms``, and a group that holds such a span, or a stage of
another layer that read no clock (a ``record()`` of a compile), has no
``self_cpu_ms`` / ``clocked_self_ms`` / ``self_wait_ms``.
The clock is the host's: where it ticks coarsely (10 ms under a
sandboxed kernel; ``tools/thread_clock.py`` says) one span's ``cpu_ms``
is a multiple of the tick and only the sums the exporter serves mean
what they say.
While a span is real and jax is loaded it is also a
``jax.profiler.TraceAnnotation``: with a profiler running, every span
lands in the device trace on its host thread's line, stamped by the
profiler's own clock.

Cost model (the whole point):

- Tracing OFF (no subscriber, no retention ring): ``TRACER.root`` is a
  bool check returning the shared ``NOOP`` singleton, and ``span()`` /
  ``record()`` are a single contextvar read — no Span object is ever
  allocated (``SPAN_ALLOCS`` is the test sentinel for that).
- Tracing ON: spans cost one object, two reads of
  ``time.monotonic()``, one ``threading.get_ident()``, for a clocked
  span (above) two reads of ``time.thread_time()`` and (with jax
  loaded) one TraceAnnotation each, paid only by requests actually
  being traced (``MTPU_TRACE_SAMPLE`` down-samples root creation;
  untraced requests fall back to NOOP).

Completed root spans become plain-dict trace records that fan out to:
a bounded ring of recent traces (``MTPU_TRACE_RING``, newest-N kept),
live PubSub subscribers (the admin NDJSON stream), and per-API
aggregates (latency percentiles + per-stage duration histograms served
by ``GET /minio/admin/v3/top/apis`` and the Prometheus exporter).
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque
from contextvars import ContextVar

from .trace import PubSub

_current: ContextVar = ContextVar("mtpu_span", default=None)

#: Request-scoped vars other layers register (rpc.rest's deadline
#: budget) so wrap_ctx carries them across pool hops alongside the span
#: — fan-out workers run in their own contextvars context and would
#: otherwise silently drop the caller's request scope.
_CARRIED: list[ContextVar] = []


def carry_var(var: ContextVar) -> None:
    """Register a contextvar for cross-thread carry in wrap_ctx.  The
    var's default must be None (None values are not re-set in the
    worker, keeping the all-defaults path zero-cost)."""
    if var not in _CARRIED:
        _CARRIED.append(var)

#: Counts every Span.__init__ — the tests' allocation sentinel proving
#: the disabled path never materialises span objects.
SPAN_ALLOCS = 0

#: Counts every jax.profiler.TraceAnnotation a span constructs: zero
#: while tracing is off, whether or not jax is loaded.
ANNOTATION_ALLOCS = 0

#: Stage-name prefix -> layer of the system (PERF.md section 3), first
#: match wins.  One table: the exporter's `layer` label, the per-layer
#: self-time metrics and the tests all read it.
LAYERS = (
    ("http.", "front_door"),
    ("engine.", "engine"), ("mp.", "engine"),
    ("storage.", "storage"), ("host.hash_batch", "storage"),
    ("coalesce.", "dispatch"), ("ipc.", "dispatch"),
    ("metalane.", "dispatch"),
    ("lane.", "lane"),
    ("device.", "device"),
)

#: Stage under which a request root's own self time (what no child span
#: covers) is aggregated.
ROOT_SELF_STAGE = "http.other"


_LAYER_OF: dict[str, str] = {}


def layer_of(stage: str) -> str:
    layer = _LAYER_OF.get(stage)
    if layer is None:
        layer = next((la for prefix, la in LAYERS
                      if stage.startswith(prefix)), "other")
        if len(_LAYER_OF) < 1024:       # stage names are code's own
            _LAYER_OF[stage] = layer
    return layer


def root_self_stage(api: str) -> str:
    """The stage a root's own self time is aggregated under: a lane
    dispatch's is its own, a request's the front door's."""
    return api if layer_of(api) == "lane" else ROOT_SELF_STAGE


#: Stages that read their thread's CPU clock though they run on their
#: parent's thread in their parent's layer, because a reader asks for
#: their own split: the lane's host-side work (`lane_host_wait_pct`
#: reads pack, h2d, launch and scatter; PERF.md section 5 the resolve's
#: program_wait and fetch beside them), the degraded read's three
#: copies (PERF.md section 5: is a copy's time page faults, on the
#: clock, or a queue for the GIL, off it) and a segment's host digest
#: of its K rows (`engine.hash`: does the hash run or queue while the
#: lane rebuilds).  Tens of spans a second.
CPU_STAGES = frozenset((
    "lane.pack", "lane.h2d", "lane.launch", "lane.program_wait",
    "lane.fetch", "lane.scatter",
    "engine.gather", "engine.assemble", "engine.join", "engine.hash"))


def _annotation(name: str):
    """A jax.profiler.TraceAnnotation for a real span, or None while
    jax is not (fully) imported: span.py never imports it itself."""
    prof = getattr(sys.modules.get("jax"), "profiler", None)
    cls = getattr(prof, "TraceAnnotation", None)
    if cls is None:
        return None
    global ANNOTATION_ALLOCS
    ANNOTATION_ALLOCS += 1
    return cls(name)


def _uncovered_s(lo: float, hi: float, children) -> float:
    """Length of [lo, hi] minus the union of the children's intervals
    clipped to it."""
    covered, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(c.t0, lo), min(c.t0 + c.dur_s, hi))
                       for c in children):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                covered += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        covered += cur_e - cur_s
    return max(0.0, (hi - lo) - covered)


#: Bound on children held per span: a pathological stream can emit
#: unbounded per-batch spans; beyond this the tree drops the extras
#: (durations still aggregate via record()'s parent check failing last).
MAX_CHILDREN = 4096


class _NoopSpan:
    """Shared do-nothing span for the disabled path. One instance,
    no state, so ``with span(...)`` costs no allocation when off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def tag(self, **kw):
        return self

    def discard(self):
        return self

    def suspend(self):
        return self

    def resume(self):
        return self


NOOP = _NoopSpan()


class Span:
    __slots__ = ("name", "tags", "t0", "dur_s", "children", "tid",
                 "cpu_s", "_c0", "_clk", "_layer", "_parent", "_token",
                 "_tracer", "_ann", "_dropped")

    def __init__(self, tracer, name: str, tags: dict | None = None):
        global SPAN_ALLOCS
        SPAN_ALLOCS += 1
        self._tracer = tracer
        self.name = name
        self.tags = tags if tags is not None else {}
        self.t0 = 0.0
        self.dur_s = 0.0
        self.children: list[Span] = []
        # The thread the span was entered on; whether it reads that
        # thread's CPU clock (the module docstring says which do), and
        # the CPU seconds inside it: None = no reading.
        self.tid = 0
        self.cpu_s: float | None = None
        self._c0 = 0.0
        self._clk = False
        self._layer = ""
        self._parent = None
        self._token = None
        self._ann = None
        self._dropped = False

    def tag(self, **kw):
        self.tags.update(kw)
        return self

    def discard(self):
        """Leave this span out of the tree when it exits (a pull that
        found its iterator exhausted is no stage)."""
        self._dropped = True
        return self

    def __enter__(self):
        p = self._parent = _current.get()
        self._token = _current.set(self)
        self._ann = _annotation(self.name)
        if self._ann is not None:
            self._ann.__enter__()
        self.tid = tid = threading.get_ident()
        if p is None:
            self._layer = layer_of(root_self_stage(self.name))
            self._clk = True
        else:
            self._layer = layer = layer_of(self.name)
            self._clk = (p.tid != tid or layer != p._layer
                         or self.name in CPU_STAGES)
        self.t0 = time.monotonic()
        if self._clk:
            self.cpu_s = 0.0
            self._c0 = time.thread_time()
        return self

    def _stop_cpu(self) -> None:
        """Close the CPU reading that __enter__ / resume() opened; none
        where the calling thread is not the one that opened it (its
        clock is another clock)."""
        if self.cpu_s is not None:
            if threading.get_ident() == self.tid:
                self.cpu_s += time.thread_time() - self._c0
            else:
                self.cpu_s = None

    def _leave(self) -> None:
        try:
            _current.reset(self._token)
        except ValueError:
            # Entered in one context, exited in another (thread hop):
            # restore the parent by value instead.
            _current.set(self._parent)

    def suspend(self):
        """Step out of an open span without ending it, and back in with
        resume(): a pipelined lane dispatch stays open from its pack to
        its resolve while the lane packs the next one.  The thread's
        CPU in between is the next batch's, not this span's."""
        self._stop_cpu()
        self._leave()
        return self

    def resume(self):
        self._token = _current.set(self)
        if self.cpu_s is not None:
            if threading.get_ident() != self.tid:
                self.cpu_s = None
            else:
                self._c0 = time.thread_time()
        return self

    def __exit__(self, et, ev, tb):
        # The clock read is a system call: made before the span's end
        # is taken, its cost lies inside the span that asked for it and
        # not in the gap before the next one.
        self._stop_cpu()
        self.dur_s = time.monotonic() - self.t0
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
            self._ann = None
        self._leave()
        if self._dropped:
            return False
        p = self._parent
        if p is not None:
            if len(p.children) < MAX_CHILDREN:
                p.children.append(self)
        else:
            self._tracer._finish_root(self, et is not None)
        return False

    def self_s(self) -> float:
        if not self.children:
            return self.dur_s
        return _uncovered_s(self.t0, self.t0 + self.dur_s, self.children)

    def _clock_group(self) -> tuple[float, float] | None:
        """(self seconds, CPU seconds to take off) of the span's clock
        group: the span and the spans below it on its thread that read
        no clock of their own (their time is time of this span's
        reading), and the readings of the clocked spans below those.
        None where a span on the thread has no reading and cannot be
        folded: it lost its reading, or it is a stage of another layer
        (a `record()` of a compile)."""
        own, sub = self.self_s(), 0.0
        for c in self.children:
            if c.tid != self.tid:
                continue                        # another thread's clock
            if c.cpu_s is not None:
                sub += c.cpu_s
                continue
            g = (None if c._clk or c._layer != self._layer
                 else c._clock_group())
            if g is None:
                return None
            own += g[0]
            sub += g[1]
        return own, sub

    def root_tag(self, key: str):
        sp = self
        while sp._parent is not None:
            sp = sp._parent
        return sp.tags.get(key)

    def to_dict(self, t_root: float | None = None) -> dict:
        if t_root is None:
            t_root = self.t0
        d = {"name": self.name,
             "start_ms": round((self.t0 - t_root) * 1e3, 4),
             "dur_ms": round(self.dur_s * 1e3, 4),
             "self_ms": round(self.self_s() * 1e3, 4)}
        if self.cpu_s is not None:
            d["cpu_ms"] = round(self.cpu_s * 1e3, 4)
            group = self._clock_group()
            if group is not None:
                own = round(group[0] * 1e3, 4)
                cpu = round(max(0.0, self.cpu_s - group[1]) * 1e3, 4)
                d["self_cpu_ms"] = cpu
                d["clocked_self_ms"] = own
                d["self_wait_ms"] = round(max(0.0, own - cpu), 4)
        if self.tags:
            d["tags"] = dict(self.tags)
        if self.children:
            d["spans"] = [c.to_dict(t_root) for c in self.children]
        return d


class TraceFilter:
    """The three server-side stream filters of `mc admin trace`:
    errors-only, request-path prefix, minimum root duration."""

    __slots__ = ("err_only", "path_prefix", "min_ms")

    def __init__(self, err_only: bool = False, path_prefix: str = "",
                 min_ms: float = 0.0):
        self.err_only = err_only
        self.path_prefix = path_prefix
        self.min_ms = min_ms

    @classmethod
    def from_query(cls, query: dict) -> "TraceFilter":
        err = str(query.get("err", query.get("errOnly", ""))
                  ).lower() in ("1", "true", "yes", "on")
        prefix = query.get("path", query.get("prefix", ""))
        try:
            # minio's threshold is a duration string; accept plain ms.
            min_ms = float(query.get("min-duration-ms",
                                     query.get("threshold", 0)) or 0)
        except ValueError:
            min_ms = 0.0
        return cls(err_only=err, path_prefix=prefix, min_ms=min_ms)

    def matches(self, rec: dict) -> bool:
        if self.err_only and not rec.get("error"):
            return False
        if self.path_prefix:
            path = str(rec.get("tags", {}).get("path", ""))
            if not path.startswith(self.path_prefix):
                return False
        if self.min_ms and rec.get("dur_ms", 0.0) < self.min_ms:
            return False
        return True


#: Stage-duration histogram bucket upper bounds, milliseconds.
BUCKETS_MS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0,
              50.0, 100.0, 250.0, 1000.0, float("inf"))

_MAX_APIS = 128        # aggregate cardinality bounds (hostile paths)
_MAX_STAGES = 64
_PCTL_WINDOW = 512     # per-API root durations kept for percentiles


class _ApiAgg:
    __slots__ = ("count", "errors", "total_ms", "self_ms", "self_cpu_ms",
                 "clocked_ms", "durs_ms", "stages")

    def __init__(self):
        self.count = 0
        self.errors = 0
        self.total_ms = 0.0
        self.self_ms = 0.0          # the roots' own self time; their
        self.self_cpu_ms = 0.0      # CPU where known, and the self time
        self.clocked_ms = 0.0       # that CPU is of (`clocked_self_ms`)
        self.durs_ms: deque = deque(maxlen=_PCTL_WINDOW)
        # stage name -> [count, total_ms, per-bucket counts, self_ms,
        #                self_cpu_ms, clocked_self_ms]
        self.stages: dict[str, list] = {}


def _wait_ms(clocked_ms: float, cpu_ms: float) -> float:
    """A stage's wait: the self time of its clocked spans less their
    CPU, floored here, on the sums: the whole ticks a coarse clock
    hands to one span and keeps from its neighbours cancel."""
    return round(max(0.0, clocked_ms - cpu_ms), 4)


def _pctl(sorted_ms: list, q: float) -> float:
    if not sorted_ms:
        return 0.0
    i = min(len(sorted_ms) - 1, int(q * (len(sorted_ms) - 1) + 0.5))
    return sorted_ms[i]


class SpanTracer:
    """Process-global span sink: retention ring + live PubSub + per-API
    aggregates.  ``enabled`` is a plain bool re-derived on every
    configure/subscribe change so the request path reads one attribute."""

    def __init__(self):
        self.pubsub = PubSub()
        self._mu = threading.Lock()
        self._ring: deque | None = None
        self._agg: dict[str, _ApiAgg] = {}
        self._stride = 1
        self._nroot = 0
        self.enabled = False
        self.configure()

    # -- configuration -------------------------------------------------------

    def configure(self, ring: int | None = None,
                  sample: float | None = None) -> None:
        """(Re)apply retention/sampling; None reads the env knobs
        MTPU_TRACE_RING (trace ring capacity, 0 = off) and
        MTPU_TRACE_SAMPLE (fraction of requests rooted, default 1)."""
        if ring is None:
            try:
                ring = int(os.environ.get("MTPU_TRACE_RING", "0") or 0)
            except ValueError:
                ring = 0
        if sample is None:
            try:
                sample = float(
                    os.environ.get("MTPU_TRACE_SAMPLE", "1") or 1)
            except ValueError:
                sample = 1.0
        with self._mu:
            old = list(self._ring) if self._ring is not None else []
            self._ring = deque(old, maxlen=ring) if ring > 0 else None
            self._stride = (max(1, round(1.0 / sample))
                            if 0.0 < sample < 1.0 else 1)
            self._refresh_enabled()

    def _refresh_enabled(self) -> None:
        self.enabled = (self._ring is not None
                        or self.pubsub.num_subscribers > 0)

    def subscribe(self, maxlen: int = 1000):
        q = self.pubsub.subscribe(maxlen)
        with self._mu:
            self._refresh_enabled()
        return q

    def unsubscribe(self, q) -> None:
        self.pubsub.unsubscribe(q)
        with self._mu:
            self._refresh_enabled()

    # -- span creation -------------------------------------------------------

    def root(self, name: str, **tags):
        """Open a request root span; NOOP when tracing is off or the
        request loses the sampling draw."""
        if not self.enabled:
            return NOOP
        if self._stride > 1:
            self._nroot += 1                 # racy increment is fine:
            if self._nroot % self._stride:   # sampling, not accounting
                return NOOP
        return Span(self, name, tags)

    # -- completion sinks ----------------------------------------------------

    def _finish_root(self, root: Span, exc: bool) -> None:
        err = exc or bool(root.tags.get("error"))
        rec = root.to_dict()
        rec["time"] = time.time()
        rec["error"] = err
        with self._mu:
            self._aggregate_locked(rec, err)
            if self._ring is not None:
                self._ring.append(rec)
        self.pubsub.publish(rec)

    def _aggregate_locked(self, rec: dict, err: bool) -> None:
        """Fold one finished root's record into its API's aggregates."""
        api = rec["name"]
        agg = self._agg.get(api)
        if agg is None:
            if len(self._agg) >= _MAX_APIS:
                return
            agg = self._agg[api] = _ApiAgg()
        agg.count += 1
        agg.errors += err
        agg.total_ms += rec["dur_ms"]
        agg.self_ms += rec["self_ms"]
        agg.self_cpu_ms += rec.get("self_cpu_ms", 0.0)
        agg.clocked_ms += rec.get("clocked_self_ms", 0.0)
        agg.durs_ms.append(rec["dur_ms"])
        stack = list(rec.get("spans", ()))
        while stack:
            sp = stack.pop()
            stack.extend(sp.get("spans", ()))
            st = agg.stages.get(sp["name"])
            if st is None:
                if len(agg.stages) >= _MAX_STAGES:
                    continue
                st = agg.stages[sp["name"]] = [
                    0, 0.0, [0] * len(BUCKETS_MS), 0.0, 0.0, 0.0]
            ms = sp["dur_ms"]
            st[0] += 1
            st[1] += ms
            st[3] += sp["self_ms"]
            st[4] += sp.get("self_cpu_ms", 0.0)
            st[5] += sp.get("clocked_self_ms", 0.0)
            for i, b in enumerate(BUCKETS_MS):
                if ms <= b:
                    st[2][i] += 1
                    break

    # -- read-side -----------------------------------------------------------

    def traces(self, filt: TraceFilter | None = None) -> list[dict]:
        """Retained trace records, oldest first."""
        with self._mu:
            recs = list(self._ring) if self._ring is not None else []
        if filt is not None:
            recs = [r for r in recs if filt.matches(r)]
        return recs

    def snapshot(self) -> dict:
        """Aggregated per-API latency + stage histograms (top/apis)."""
        apis = {}
        with self._mu:
            for api, a in sorted(self._agg.items()):
                durs = sorted(a.durs_ms)
                apis[api] = {
                    "count": a.count,
                    "errors": a.errors,
                    "total_ms": round(a.total_ms, 4),
                    "self_ms": round(a.self_ms, 4),
                    "self_cpu_ms": round(a.self_cpu_ms, 4),
                    "self_wait_ms": _wait_ms(a.clocked_ms,
                                             a.self_cpu_ms),
                    "avg_ms": round(a.total_ms / a.count, 4)
                    if a.count else 0.0,
                    "p50_ms": round(_pctl(durs, 0.50), 4),
                    "p90_ms": round(_pctl(durs, 0.90), 4),
                    "p99_ms": round(_pctl(durs, 0.99), 4),
                    "stages": {
                        name: {"count": st[0],
                               "total_ms": round(st[1], 4),
                               "self_ms": round(st[3], 4),
                               "self_cpu_ms": round(st[4], 4),
                               "self_wait_ms": _wait_ms(st[5], st[4]),
                               "buckets": list(st[2])}
                        for name, st in sorted(a.stages.items())},
                }
        return {"apis": apis,
                "bucket_bounds_ms": [b for b in BUCKETS_MS
                                     if b != float("inf")]}

    def reset(self) -> None:
        """Drop retained traces and aggregates (tests/bench)."""
        with self._mu:
            if self._ring is not None:
                self._ring.clear()
            self._agg.clear()
            self._nroot = 0


TRACER = SpanTracer()


# -- module-level fast-path helpers (the instrumentation surface) -----------

def span(name: str):
    """Nested stage span under the current request; NOOP (one
    contextvar read, zero allocation) when no request is being traced."""
    if _current.get() is None:
        return NOOP
    return Span(TRACER, name)


def root_span(name: str, **tags):
    return TRACER.root(name, **tags)


def span_or_root(name: str, **tags):
    """A nested span where the caller is inside a traced request, else
    a root of its own (a lane thread's dispatch)."""
    if _current.get() is not None:
        return Span(TRACER, name, tags)
    return TRACER.root(name, **tags)


def _attach(parent: Span, name: str, start: float, seconds: float,
            tags: dict | None) -> Span:
    """A pre-measured child: it read no clock, so its time is time of
    the reading of the clocked span above it where that is its layer's,
    and unknown where it is not (Span._clock_group)."""
    sp = Span(TRACER, name, tags)
    sp.t0 = start
    sp.dur_s = seconds
    sp.tid = threading.get_ident()
    sp._layer = layer_of(name)
    if len(parent.children) < MAX_CHILDREN:
        parent.children.append(sp)
    return sp


def record(name: str, seconds: float, **tags) -> None:
    """Attach a pre-measured child span that ends now (a queue wait, a
    compile, per-drive I/O) to the current span, if any."""
    parent = _current.get()
    if parent is not None:
        _attach(parent, name, time.monotonic() - seconds, seconds,
                tags or None)


def bracket(name: str, start: float, end: float) -> None:
    """Attach a stage measured by the caller's own clock reads
    (time.monotonic()) as a child of the current span, and move under
    it the children the current span gained inside [start, end]: the
    waits and drive calls the stage made nest where they belong, so
    their time is not counted as the stage's own too."""
    parent = _current.get()
    if parent is None:
        return
    kids = parent.children
    inside = [c for c in kids
              if c.t0 >= start - 1e-6 and c.t0 + c.dur_s <= end + 1e-6]
    for c in inside:
        kids.remove(c)
    _attach(parent, name, start, end - start, None).children = inside


def request_id():
    """The handler's request id of the traced request the caller runs
    under (None when untraced)."""
    cur = _current.get()
    return None if cur is None else cur.root_tag("request_id")


def current():
    return _current.get()


def wrap_ctx(fn):
    """Carry the current span — plus every carry_var-registered
    request-scoped var (deadline budgets) — across a thread-pool hop:
    returns fn bound to the calling context's values, or fn unchanged
    when nothing is set (the zero-cost default).  Values are re-set in
    the worker's own context rather than via
    contextvars.copy_context().run — a single Context object cannot be
    entered concurrently from the many pool threads a fan-out uses."""
    cur = _current.get()
    extras = [(v, v.get()) for v in _CARRIED]
    if cur is None and all(val is None for _, val in extras):
        return fn

    def run(*a, **kw):
        tokens = [(v, v.set(val)) for v, val in extras
                  if val is not None]
        token = _current.set(cur) if cur is not None else None
        try:
            return fn(*a, **kw)
        finally:
            if token is not None:
                _current.reset(token)
            for v, tk in reversed(tokens):
                v.reset(tk)
    return run


def timed_iter(gen, name: str):
    """Wrap a batch generator so the time blocked producing each item
    is a child span of the consumer's current span (spans the producer
    opens nest under it).  Returns the generator unchanged when
    untraced."""
    if _current.get() is None:
        return gen

    def timed():
        it = iter(gen)
        while True:
            with span(name) as sp:
                try:
                    item = next(it)
                except StopIteration:
                    sp.discard()
                    return
            yield item
    return timed()


def flat(rec: dict) -> dict | None:
    """The per-request line `GET /minio/admin/v3/trace` serves, from a
    request root's record; None for a root that is no request (a lane
    dispatch)."""
    tags = rec.get("tags", {})
    if "method" not in tags:
        return None
    return {"time": rec["time"], "api": rec["name"],
            "method": tags["method"], "path": tags.get("path", ""),
            "statusCode": tags.get("status", 0),
            "durationMs": round(rec["dur_ms"], 3),
            "requestSize": tags.get("request_size", 0),
            "responseSize": tags.get("response_size", 0),
            "sourceIp": tags.get("source_ip", "")}


# -- analysis helpers (bench attribution, tests) ----------------------------

def flatten(rec: dict) -> dict:
    """Summed duration (ms) per span name over a whole trace record."""
    out: dict[str, float] = {}

    def walk(d):
        for c in d.get("spans", ()):
            out[c["name"]] = out.get(c["name"], 0.0) + c["dur_ms"]
            walk(c)
    walk(rec)
    return out


def coverage(rec: dict) -> float:
    """Fraction of root wall time accounted for by its direct children
    (capped at 1.0 — pipelined children legitimately overlap)."""
    total = rec.get("dur_ms", 0.0)
    if not total:
        return 0.0
    return min(1.0, sum(c["dur_ms"] for c in rec.get("spans", ()))
               / total)
