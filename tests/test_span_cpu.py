"""A span says whether its thread ran or waited (PR 40).

Thread-CPU time on every real span (`cpu_ms`, `self_cpu_ms`,
`self_wait_ms`; absent where there is no reading, never guessed), the
two exported families that sum them, the degraded read's copies as
spans and as `mtpu_get_fresh_buffer_bytes_total{site}`, the lane's
resolve in its three parts, the eight metric files that read them, the
stall watcher and the ring's trees through the front door.  CPU
backend: what is held here is bookkeeping, not a time.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from minio_tpu.engine import segarena, shardmath
from minio_tpu.engine.erasure_set import BLOCK_SIZE, ErasureSet
from minio_tpu.engine.pools import ServerPools
from minio_tpu.engine.sets import ErasureSets
from minio_tpu.observe import span as ospan
from minio_tpu.observe.metrics import (DATA_PATH, GET_FRESH_SITES,
                                       MetricsRegistry)
from minio_tpu.ops import coalesce
from minio_tpu.server.client import S3Client
from minio_tpu.server.server import S3Server
from minio_tpu.server.sigv4 import Credentials
from minio_tpu.storage.drive import LocalDrive

ACCESS, SECRET = "cpuadmin", "cpuadmin-secret"
BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
NEW_METRIC_FILES = [
    "engine_self_wait_ms_per_gb.get", "storage_self_wait_ms_per_gb.get",
    "front_door_self_wait_ms_per_gb.get", "engine_self_wait_ms_per_gb.put",
    "storage_self_wait_ms_per_gb.put", "front_door_self_wait_ms_per_gb.put",
    "lane_host_wait_pct", "get_fresh_buffer_bytes_per_byte"]


@pytest.fixture(autouse=True)
def tracer_reset():
    yield
    ospan.TRACER.configure(ring=0, sample=1.0)
    ospan.TRACER.reset()


def burn(cpu_s: float) -> None:
    """Run until the calling thread's CPU clock has advanced `cpu_s`."""
    end = time.thread_time() + cpu_s
    while time.thread_time() < end:
        sum(range(2000))


def traced(fn) -> dict:
    """The record of one root around `fn()`."""
    ospan.TRACER.configure(ring=8, sample=1.0)
    with ospan.TRACER.root("api.Test"):
        fn()
    return ospan.TRACER.traces()[-1]


def child(rec: dict, name: str) -> dict:
    stack = [rec]
    while stack:
        sp = stack.pop()
        if sp["name"] == name:
            return sp
        stack.extend(sp.get("spans", ()))
    raise AssertionError(f"no span {name} in {rec['name']}")


# -- the clock on a span ------------------------------------------------------------

class TestSpanCpu:
    def test_a_sleeping_span_waited(self):
        def body():
            with ospan.span("engine.sleep"):
                time.sleep(0.05)
        sp = child(traced(body), "engine.sleep")
        assert sp["dur_ms"] >= 50
        assert sp["cpu_ms"] < 10 and sp["self_cpu_ms"] < 10
        assert sp["self_wait_ms"] > 35

    def test_a_busy_span_ran(self):
        def body():
            with ospan.span("engine.busy"):
                burn(0.05)
        sp = child(traced(body), "engine.busy")
        assert sp["cpu_ms"] >= 49 and sp["self_cpu_ms"] == sp["cpu_ms"]
        # What is left of the wall time is what the thread was kept
        # off the CPU by its neighbours: never more than that.
        assert sp["self_wait_ms"] == pytest.approx(
            max(0.0, sp["self_ms"] - sp["self_cpu_ms"]), abs=1e-3)
        assert sp["self_wait_ms"] <= sp["self_ms"] - 49

    def test_a_child_of_another_layer_on_the_same_thread_is_subtracted(self):
        def body():
            with ospan.span("engine.parent"):
                burn(0.01)
                with ospan.span("storage.child"):
                    burn(0.03)
        rec = traced(body)
        parent, kid = child(rec, "engine.parent"), child(rec, "storage.child")
        assert kid["cpu_ms"] >= 29 and parent["cpu_ms"] >= 39
        assert parent["self_cpu_ms"] == pytest.approx(
            parent["cpu_ms"] - kid["cpu_ms"], abs=1e-3)
        assert parent["clocked_self_ms"] == parent["self_ms"]

    def test_a_child_of_the_same_layer_reads_no_clock_and_is_folded(self):
        def body():
            with ospan.span("engine.parent"):
                burn(0.01)
                with ospan.span("engine.child"):
                    burn(0.02)
                    with ospan.span("storage.read"):
                        burn(0.01)
        rec = traced(body)
        parent, kid = child(rec, "engine.parent"), child(rec, "engine.child")
        grandkid = child(rec, "storage.read")
        for field in ("cpu_ms", "self_cpu_ms", "clocked_self_ms",
                      "self_wait_ms"):
            assert field not in kid
        # The child's time is time of the parent's reading; the clocked
        # span below the child comes off it.
        assert parent["clocked_self_ms"] == pytest.approx(
            parent["self_ms"] + kid["self_ms"], abs=1e-3)
        assert parent["self_cpu_ms"] == pytest.approx(
            parent["cpu_ms"] - grandkid["cpu_ms"], abs=1e-3)
        assert parent["self_cpu_ms"] >= 29
        # The families: the child's stage has its self time and no part
        # of either; the layer's sums still add up.
        stages = ospan.TRACER.snapshot()["apis"]["api.Test"]["stages"]
        assert stages["engine.child"]["self_ms"] == kid["self_ms"]
        assert stages["engine.child"]["self_cpu_ms"] == 0.0
        assert stages["engine.child"]["self_wait_ms"] == 0.0
        head = stages["engine.parent"]
        assert head["self_cpu_ms"] + head["self_wait_ms"] == pytest.approx(
            head["self_ms"] + kid["self_ms"], abs=1e-2)

    def test_a_child_on_a_pool_thread_is_not(self):
        def work():
            with ospan.span("engine.worker"):    # its parent's layer,
                burn(0.03)                       # another thread's clock

        def body():
            with ospan.span("engine.parent"), \
                    ThreadPoolExecutor(1) as pool:
                pool.submit(ospan.wrap_ctx(work)).result()
        rec = traced(body)
        parent, kid = child(rec, "engine.parent"), child(rec, "engine.worker")
        assert kid["cpu_ms"] >= 29
        # The pool thread's CPU is its own: the parent keeps all of its.
        assert parent["self_cpu_ms"] == parent["cpu_ms"]
        assert parent["cpu_ms"] < kid["cpu_ms"]
        assert parent["clocked_self_ms"] == parent["self_ms"]

    def test_a_stage_of_another_layer_with_no_reading_leaves_the_parent_absent(
            self):
        def body():
            with ospan.span("engine.unknown"):
                ospan.record("device.compile", 0.01)
            with ospan.span("engine.known"):
                ospan.record("engine.assemble", 0.01)
        rec = traced(body)
        unknown = child(rec, "engine.unknown")
        assert "cpu_ms" in unknown
        for field in ("self_cpu_ms", "clocked_self_ms", "self_wait_ms"):
            assert field not in unknown
        assert "cpu_ms" not in child(rec, "device.compile")
        # A pre-measured stage of the parent's own layer is the parent's
        # time: the reading covers both.
        known = child(rec, "engine.known")
        assert known["self_cpu_ms"] == known["cpu_ms"]
        assert known["clocked_self_ms"] == pytest.approx(
            known["self_ms"] + 10.0, abs=0.01)
        # ... and the unknown adds nothing to either family.
        stages = ospan.TRACER.snapshot()["apis"]["api.Test"]["stages"]
        assert stages["engine.unknown"]["self_ms"] > 0
        assert stages["engine.unknown"]["self_cpu_ms"] == 0.0
        assert stages["engine.unknown"]["self_wait_ms"] == 0.0
        assert stages["device.compile"]["self_wait_ms"] == 0.0
        assert stages["engine.known"]["self_cpu_ms"] == known["self_cpu_ms"]

    def test_a_bracket_is_its_parents_time_and_takes_the_spans_inside(self):
        def body():
            with ospan.span("engine.read_part"):
                t0 = time.monotonic()
                with ospan.span("storage.read"):
                    burn(0.01)
                burn(0.02)
                ospan.bracket("engine.read", t0, time.monotonic())
        rec = traced(body)
        part, read = child(rec, "engine.read_part"), child(rec, "engine.read")
        assert [c["name"] for c in read["spans"]] == ["storage.read"]
        assert "cpu_ms" not in read
        assert part["self_cpu_ms"] == pytest.approx(
            part["cpu_ms"] - read["spans"][0]["cpu_ms"], abs=1e-3)
        assert part["self_cpu_ms"] >= 19
        assert part["clocked_self_ms"] == pytest.approx(
            part["self_ms"] + read["self_ms"], abs=1e-3)

    def test_only_the_spans_a_reader_needs_read_the_clock(self, monkeypatch):
        reads = []
        real = time.thread_time

        def counted():
            reads.append(ospan.current().name)
            return real()

        def body():
            with ospan.span("http.auth"):            # the root's layer
                pass
            with ospan.span("engine.get_object"):    # a change of layer
                with ospan.span("engine.read_part"):     # none
                    with ospan.span("storage.read"):     # a change
                        with ospan.span("storage.open"):     # none
                            pass
                    with ospan.span("engine.gather"):    # CPU_STAGES
                        pass
                    ospan.record("engine.assemble", 0.001)

        monkeypatch.setattr(time, "thread_time", counted)
        traced(body)
        monkeypatch.undo()
        # Two reads a clocked span, each inside the span that made it.
        assert reads == ["api.Test", "engine.get_object", "storage.read",
                         "storage.read", "engine.gather", "engine.gather",
                         "engine.get_object", "api.Test"]
        assert ospan.CPU_STAGES >= {
            "lane.pack", "lane.h2d", "lane.launch", "lane.scatter"}

    def test_the_wait_is_floored_on_a_stages_sums_not_a_span_at_a_time(
            self, monkeypatch):
        # A clock that ticks: the first of two 5 ms reads is handed a
        # whole 10 ms tick, the second none.
        ticks = iter([0.0, 0.0,             # root, engine.p enter
                      0.0, 0.010,           # the first read
                      0.010, 0.010,         # the second
                      0.010, 0.010])        # engine.p, root exit

        def body():
            with ospan.span("engine.p"):
                for _ in range(2):
                    with ospan.span("storage.read"):
                        time.sleep(0.005)

        monkeypatch.setattr(time, "thread_time", lambda: next(ticks))
        rec = traced(body)
        monkeypatch.undo()
        first, second = child(rec, "engine.p")["spans"]
        assert first["self_wait_ms"] == 0.0 and first["cpu_ms"] == 10.0
        assert second["self_wait_ms"] == second["self_ms"] >= 5.0
        st = ospan.TRACER.snapshot()["apis"]["api.Test"]["stages"][
            "storage.read"]
        assert st["self_cpu_ms"] == 10.0
        # One span at a time the wait would read >= 5 ms; on the sums
        # it is what the two reads took over 10 ms.
        assert st["self_wait_ms"] == pytest.approx(
            st["self_ms"] - 10.0, abs=1e-3)
        assert st["self_wait_ms"] < 2.5
        assert st["self_cpu_ms"] + st["self_wait_ms"] == pytest.approx(
            st["self_ms"], abs=1e-3)

    def test_suspend_and_resume_charge_nothing_in_between(self):
        ospan.TRACER.configure(ring=8, sample=1.0)
        root = ospan.TRACER.root("lane.dispatch").__enter__()
        burn(0.01)
        root.suspend()
        assert ospan.current() is None
        burn(0.05)                      # the next batch's work
        root.resume()
        burn(0.01)
        root.__exit__(None, None, None)
        rec = ospan.TRACER.traces()[-1]
        assert 19 <= rec["cpu_ms"] < 45
        assert rec["dur_ms"] >= 69

    def test_a_span_left_on_another_thread_has_no_reading(self):
        ospan.TRACER.configure(ring=8, sample=1.0)
        root = ospan.TRACER.root("api.Hop")
        entered, left = threading.Event(), threading.Event()

        def enter():            # alive until the span has been left:
            root.__enter__()    # its ident is not handed out again
            entered.set()
            left.wait(10)

        t = threading.Thread(target=enter)
        t.start()
        entered.wait(10)
        other = threading.Thread(target=root.__exit__,
                                 args=(None, None, None))
        other.start()
        other.join()
        left.set()
        t.join()
        rec = ospan.TRACER.traces()[-1]
        assert rec["name"] == "api.Hop" and rec["dur_ms"] >= 0
        for field in ("cpu_ms", "self_cpu_ms", "clocked_self_ms",
                      "self_wait_ms"):
            assert field not in rec


# -- the degraded read's copies -----------------------------------------------------

SIZE = 2 * BLOCK_SIZE + 4321            # two full blocks and a tail


@pytest.fixture
def device_codec(monkeypatch):
    """The device codec on the CPU backend, through cold lanes."""
    monkeypatch.setattr(shardmath, "platform", lambda: (True, False))
    monkeypatch.delenv("MTPU_MESH", raising=False)
    monkeypatch.setenv("MTPU_DEVICES", "1")
    coalesce.reset()
    yield
    coalesce.reset()


def make_set(tmp_path, k: int, m: int, size: int, lose: int):
    drives = [LocalDrive(str(tmp_path / f"d{i}")) for i in range(k + m)]
    es = ErasureSet(drives, default_parity=m)
    es.make_bucket("b")
    body = np.random.default_rng([40, k, m]).bytes(size)
    fi = es.put_object("b", "o", body)
    dist = fi.erasure.distribution
    for p in sorted(range(es.n), key=lambda p: dist[p])[:lose]:
        for dirpath, _, names in os.walk(os.path.join(drives[p].root, "b")):
            for n in names:
                if n.startswith("part."):
                    os.unlink(os.path.join(dirpath, n))
    return es, fi, body


def fresh() -> dict:
    return DATA_PATH.snapshot()["get_fresh_buffer_bytes"]


@pytest.fixture
def empty_arenas(monkeypatch):
    """A segment pool of the test's own: whatever its first read leases
    has to be mapped, so its sizes read as fresh whatever ran before."""
    monkeypatch.setattr(segarena, "POOL", segarena.SegmentArenas())


def fresh_growth(fn) -> dict:
    """What `fn()` grew each site's fresh counter by."""
    before = fresh()
    fn()
    return {s: fresh()[s] - before[s] for s in GET_FRESH_SITES}


@pytest.mark.parametrize("k,m", [(2, 2), (3, 3)], ids=["2+2", "3+3"])
def test_degraded_read_names_and_counts_its_copies(device_codec,
                                                   empty_arenas, tmp_path,
                                                   k, m):
    """One data shard gone: the gather, the assembly and the join are
    spans under `engine.read_part`, and each site's counter grows by
    the bytes of what was allocated there."""
    es, fi, body = make_set(tmp_path, k, m, SIZE, lose=1)
    shard = fi.erasure.shard_size
    assert (BLOCK_SIZE % k == 0) == (k == 2)
    tail_shard = -(-4321 // k)
    ospan.TRACER.configure(ring=8, sample=1.0)

    def get():
        with ospan.TRACER.root("api.GetObject"):
            _, got = es.get_object("b", "o")
        assert bytes(got) == body
    grew = fresh_growth(get)
    copied = {"gather": 2 * k * shard,
              "assemble": 2 * k * shard + k * tail_shard}
    # The K rows come into one buffer: two frames and the tail's a row.
    rows = k * (2 * (32 + shard) + 32 + tail_shard)
    # The join copies the pieces straight into the response's buffer.
    assert grew == {**copied, "join": 0, "response": SIZE, "read": rows}
    # The rows, `x` and `y` again, out of the pool.
    assert fresh_growth(get) == {"gather": 0, "assemble": k * tail_shard,
                                 "join": 0, "response": SIZE, "read": 0}
    for rec in ospan.TRACER.traces()[-2:]:
        # The spans' `bytes` are what was copied there, whatever had
        # to be mapped for it.
        part = child(rec, "engine.read_part")
        names = [c["name"] for c in part["spans"]]
        for name in ("engine.gather", "engine.assemble", "engine.join"):
            assert names.count(name) == 1, names
        assert child(part, "engine.gather")["tags"]["bytes"] == \
            copied["gather"]
        assert child(part, "engine.assemble")["tags"]["bytes"] == \
            copied["assemble"]
        join = child(part, "engine.join")["tags"]
        assert join == {"bytes": SIZE, "pieces": 2 if k == 2 else 3}
    for name in ("engine.gather", "engine.assemble", "engine.join"):
        sp = child(part, name)
        assert sp["self_cpu_ms"] + sp["self_wait_ms"] == pytest.approx(
            sp["self_ms"], abs=0.01) or sp["self_wait_ms"] == 0.0
    text = MetricsRegistry().render()
    for site in GET_FRESH_SITES:        # rendered with six digits
        (line,) = [ln for ln in text.splitlines() if ln.startswith(
            f'mtpu_get_fresh_buffer_bytes_total{{site="{site}"}} ')]
        assert float(line.split()[-1]) == pytest.approx(fresh()[site],
                                                        rel=1e-5)


def test_degraded_read_on_the_fused_host_path_gathers_nothing(empty_arenas,
                                                              tmp_path):
    """The host's one native pass writes `y` itself: no `x`."""
    es, fi, body = make_set(tmp_path, 2, 2, SIZE, lose=1)
    if es.math.host_fused(2, 2, "mxh256") is None:
        pytest.skip("no native library here")
    grew = fresh_growth(lambda: es.get_object("b", "o"))
    assert grew == {"gather": 0,
                    "assemble": 2 * BLOCK_SIZE + 2 * -(-4321 // 2),
                    "join": 0, "response": SIZE, "read": 0}
    assert bytes(es.get_object("b", "o")[1]) == body


def test_healthy_read_that_hands_out_a_view_joins_nothing(device_codec,
                                                          empty_arenas,
                                                          monkeypatch,
                                                          tmp_path):
    # No hedge: on a loaded host a parity spare that wins the race sends
    # the read through the decode path, which gathers.
    monkeypatch.setenv("MTPU_HEDGE", "0")
    es, fi, body = make_set(tmp_path, 2, 2, 2 * BLOCK_SIZE, lose=0)
    got = []
    grew = fresh_growth(lambda: got.append(es._read_part(
        "b", "o", fi, part_number=1, offset=0, length=2 * BLOCK_SIZE)))
    assert isinstance(got[0], memoryview) and bytes(got[0]) == body
    shard = fi.erasure.shard_size
    assert grew == {"gather": 0, "assemble": 2 * BLOCK_SIZE, "join": 0,
                    "response": 0, "read": 2 * 2 * (32 + shard)}


def test_degraded_get_with_tracing_off_allocates_no_span(device_codec,
                                                         tmp_path):
    es, fi, body = make_set(tmp_path, 2, 2, SIZE, lose=1)
    assert not ospan.TRACER.enabled
    before = (ospan.SPAN_ALLOCS, ospan.ANNOTATION_ALLOCS)
    _, got = es.get_object("b", "o")
    assert bytes(got) == body
    assert (ospan.SPAN_ALLOCS, ospan.ANNOTATION_ALLOCS) == before


# -- the lane's resolve -------------------------------------------------------------

def test_resolve_in_three_parts_under_device_wait(device_codec, tmp_path):
    """A degraded GET through the lane thread: `lane.device_wait` holds
    the wait for the program, the fetch and the scatter, each with the
    lane thread's CPU; the lane's six states still sum to its age."""
    es, fi, body = make_set(tmp_path, 2, 2, SIZE, lose=1)
    es.get_object("b", "o")                         # compile
    coalesce.get()._ema = 2.0           # traffic: queue, do not inline
    ospan.TRACER.configure(ring=16, sample=1.0)
    with ospan.TRACER.root("api.GetObject", request_id="rid-40"):
        _, got = es.get_object("b", "o")
    assert bytes(got) == body
    deadline = time.monotonic() + 10
    lanes = []
    while not lanes and time.monotonic() < deadline:
        lanes = [r for r in ospan.TRACER.traces()
                 if r["name"] == "lane.dispatch"
                 and "rid-40" in r["tags"]["members"]]
        time.sleep(0.01)
    assert lanes
    rec = lanes[0]
    assert [c["name"] for c in rec["spans"]] == [
        "lane.pack", "lane.h2d", "lane.launch", "lane.device_wait"]
    wait = child(rec, "lane.device_wait")
    assert [c["name"] for c in wait["spans"]] == [
        "lane.program_wait", "lane.fetch", "lane.scatter"]
    # The six stages of span.CPU_STAGES and the root read the clock;
    # `lane.device_wait`, their parent in their layer, does not: its
    # own few microseconds are the root's.
    for sp in [rec, *rec["spans"][:3], *wait["spans"]]:
        assert sp["self_cpu_ms"] >= 0 and sp["self_wait_ms"] >= 0, sp
        assert (sp["clocked_self_ms"] == sp["self_ms"]) == (sp is not rec)
    assert "cpu_ms" not in wait
    assert rec["clocked_self_ms"] == pytest.approx(
        rec["self_ms"] + wait["self_ms"], abs=1e-3)
    assert sum(c["dur_ms"] for c in wait["spans"]) <= wait["dur_ms"] + 0.01
    time.sleep(0.05)                                # parked again
    lane = coalesce.get().lane(0)
    state_s = lane.state_seconds()
    assert set(state_s) == set(lane.STATES) and len(state_s) == 6
    assert sum(state_s.values()) == pytest.approx(
        time.monotonic() - lane.t_created, rel=0.01)
    assert state_s["device_wait"] > 0
    # What the server's stall report reads, with no lock of a dispatch.
    (dev, state, seconds), = coalesce.lanes_report()
    assert (dev, state) == (0, "no_work") and set(seconds) == set(state_s)


# -- the families and the files that read them --------------------------------------

def load_run():
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "bench_run", os.path.join(BENCH, "run.py"))
        run = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(run)
    finally:
        sys.path.remove(BENCH)
    return run


@pytest.fixture(scope="module")
def scrape(tmp_path_factory):
    """/minio/v2/metrics/node after one traced PUT, GET and degraded
    GET through the front door with the device codec on, as
    benchmark/run.py parses it."""
    tmp = tmp_path_factory.mktemp("scrape")
    mp = pytest.MonkeyPatch()
    mp.setattr(shardmath, "platform", lambda: (True, False))
    mp.delenv("MTPU_MESH", raising=False)
    mp.setenv("MTPU_DEVICES", "1")
    mp.setenv("MTPU_HOTCACHE", "0")
    coalesce.reset()
    run = load_run()
    drives = [LocalDrive(str(tmp / f"d{i}")) for i in range(4)]
    srv = S3Server(ServerPools([ErasureSets(drives, set_drive_count=4)]),
                   Credentials(ACCESS, SECRET)).start()
    try:
        cli = S3Client(srv.endpoint, ACCESS, SECRET)
        cli.make_bucket("files")
        ospan.TRACER.configure(ring=32, sample=1.0)
        body = np.random.default_rng(40).bytes(SIZE)
        cli.put_object("files", "o", body)
        assert cli.get_object("files", "o") == body
        for dirpath, _, names in os.walk(os.path.join(drives[0].root,
                                                      "files")):
            for n in names:
                if n.startswith("part."):
                    os.unlink(os.path.join(dirpath, n))
        assert cli.get_object("files", "o") == body
        time.sleep(0.1)                  # the lane's roots have ended
        st, _, data = cli.request("GET", "/minio/v2/metrics/node")
        assert st == 200
        text = data.decode()
        out = {}
        for line in text.splitlines():
            name, _, value = line.rpartition(" ")
            if name.startswith("mtpu_"):
                out[name] = float(value)
        yield run, out, text
    finally:
        srv.shutdown()
        ospan.TRACER.configure(ring=0, sample=1.0)
        ospan.TRACER.reset()
        coalesce.reset()
        mp.undo()


def specs(q) -> list[str]:
    return [s for x in q for s in specs(x)] if isinstance(q, list) else [q]


@pytest.mark.parametrize("name", NEW_METRIC_FILES)
def test_metric_file_names_families_the_scrape_has(scrape, name):
    run, metrics, _ = scrape
    with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
        m = json.load(f)
    assert m["kind"] == "ratio" and m["name"] == name
    for spec in specs(m["num"]) + specs(m["den"]):
        kind, _, what = spec.partition(":")
        if kind == "counter":
            assert run.counter(metrics, what) is not None, spec
        else:
            assert spec in ("client:get_bytes", "client:put_bytes"), spec
    q = {"metrics0": {}, "metrics1": metrics,
         "client": {"get_bytes": 1e9, "put_bytes": 1e9}}
    assert run.read_metric(m, q, {}) >= 0
    # A program without the families: nothing to read, nothing raised.
    assert run.read_metric(m, dict(q, metrics1={"mtpu_up": 1.0}), {}) is None


def test_the_two_families_render_beside_self_ms(scrape):
    _, metrics, text = scrape
    for stage, layer in (("engine.read_part", "engine"),
                         ("storage.read", "storage"),
                         ("http.other", "front_door")):
        labels = f'{{api="api.GetObject",stage="{stage}",layer="{layer}"}}'
        own = metrics["mtpu_trace_stage_self_ms_total" + labels]
        cpu = metrics["mtpu_trace_stage_self_cpu_ms_total" + labels]
        wait = metrics["mtpu_trace_stage_self_wait_ms_total" + labels]
        assert own > 0 and cpu >= 0 and wait >= 0
        if stage == "storage.read":     # no pre-measured child: all known
            assert cpu + wait >= 0.999 * own
    run = scrape[0]
    for stage in ("lane.pack", "lane.h2d", "lane.launch", "lane.scatter",
                  "lane.program_wait", "lane.fetch"):
        # Under whichever root ran the dispatch: the lane's, or inline
        # the request's.
        for fam in ("self_ms", "self_cpu_ms", "self_wait_ms"):
            assert run.counter(
                metrics, f"mtpu_trace_stage_{fam}_total"
                f"{{stage={stage},layer=lane}}") is not None, (fam, stage)
    assert "\nmtpu_request_stall_episodes_total " in text


# -- the stall watcher and the ring's trees -----------------------------------------

@pytest.fixture()
def stack(tmp_path):
    drives = [LocalDrive(str(tmp_path / f"d{i}")) for i in range(4)]
    srv = S3Server(ServerPools([ErasureSets(drives, set_drive_count=4)]),
                   Credentials(ACCESS, SECRET))
    yield srv, S3Client(srv.endpoint, ACCESS, SECRET)
    srv.shutdown()


def stick(srv) -> None:
    """`GET /minio/health/live` holds its handler for 4.5 s."""
    real = srv._dispatch_internal

    def blocking(req, path, query):
        if path == "/minio/health/live":
            time.sleep(4.5)
        return real(req, path, query)

    srv._dispatch_internal = blocking


def test_a_stall_leaves_evidence_once_per_episode(stack, capfd):
    srv, cli = stack
    stick(srv)
    srv.start()
    episodes = lambda: DATA_PATH.snapshot()["request_stall_episodes"]  # noqa: E731
    before = episodes()
    time.sleep(3.2)     # nothing in flight for longer than the limit ...
    until = time.monotonic() + 1.5
    while time.monotonic() < until:     # ... then requests that complete
        assert cli.request("GET", "/minio/health/ready")[0] == 200
    assert episodes() == before
    assert cli.request("GET", "/minio/health/live")[0] == 200
    assert episodes() == before + 1
    text = capfd.readouterr().err
    assert text.count(" 1 in flight, nothing began, completed or moved") == 1
    assert "MemAvailable" in text
    assert "in blocking" in text                    # the stuck handler
    assert text.count("end of stacks") == 1
    assert cli.request("GET", "/minio/health/ready")[0] == 200
    time.sleep(1.2)
    assert episodes() == before + 1
    assert "request stall" not in capfd.readouterr().err


def test_a_request_that_begins_is_progress(stack, capfd):
    """One handler stuck for 4.5 s while others begin and complete
    beside it: the process moves, so no episode."""
    srv, cli = stack
    stick(srv)
    srv.start()
    before = DATA_PATH.snapshot()["request_stall_episodes"]
    stuck = threading.Thread(
        target=cli.request, args=("GET", "/minio/health/live"))
    stuck.start()
    other = S3Client(srv.endpoint, ACCESS, SECRET)
    while stuck.is_alive():
        assert other.request("GET", "/minio/health/ready")[0] == 200
        time.sleep(0.2)
    stuck.join()
    time.sleep(1.1)
    assert DATA_PATH.snapshot()["request_stall_episodes"] == before
    assert "request stall" not in capfd.readouterr().err


def test_the_watcher_outlives_a_look_that_fails(stack, capfd, monkeypatch):
    srv, cli = stack
    def broken(stuck, idle_s):
        raise RuntimeError("no report")

    stick(srv)
    monkeypatch.setattr(srv, "_report_stall", broken)
    srv.start()
    assert cli.request("GET", "/minio/health/live")[0] == 200
    time.sleep(1.1)
    text = capfd.readouterr().err
    assert text.count("stall watcher: a look failed") == 1
    assert "RuntimeError: no report" in text
    monkeypatch.undo()
    assert srv._stall_thread.is_alive()
    before = DATA_PATH.snapshot()["request_stall_episodes"]
    assert cli.request("GET", "/minio/health/live")[0] == 200
    assert DATA_PATH.snapshot()["request_stall_episodes"] == before + 1
    assert "nothing began, completed or moved" in capfd.readouterr().err


def test_a_streamed_response_marks_progress_chunk_by_chunk(stack):
    srv, _ = stack
    srv.start()                 # the fixture shuts a started server down
    closed = []

    def chunks():
        try:
            yield b"a"
            yield b"b"
        finally:
            closed.append(True)

    srv._last_progress = 0.0
    it = srv._marking_progress(chunks())
    assert next(it) == b"a" and srv._last_progress > 0.0
    srv._last_progress = 0.0
    assert next(it) == b"b" and srv._last_progress > 0.0
    it.close()
    assert closed == [True]


def test_an_open_stream_is_no_stall(stack, capfd):
    srv, cli = stack
    srv.start()
    before = DATA_PATH.snapshot()["request_stall_episodes"]
    st, _, _ = cli.request("POST", "/minio/admin/v3/trace",
                           query={"duration": "4.2"})
    assert st == 200
    assert DATA_PATH.snapshot()["request_stall_episodes"] == before
    assert "request stall" not in capfd.readouterr().err


def test_the_rings_trees_through_the_front_door(stack, capsys):
    """`GET /minio/admin/v3/trace?trees=1`: whole records, the lane's
    roots too, and the flat poll's queue keeps what it had."""
    srv, cli = stack
    srv.start()
    ospan.TRACER.configure(ring=16, sample=1.0)
    st, _, data = cli.request("GET", "/minio/admin/v3/trace")
    assert st == 200 and json.loads(data) == {"trace": []}  # subscribes
    cli.make_bucket("trees")
    cli.put_object("trees", "o", b"x" * 300_000)
    with ospan.TRACER.root("lane.dispatch", device=0, program="p",
                           targets=2, members=["r"]):
        pass
    for _ in range(2):                  # reads, does not drain
        st, _, data = cli.request("GET", "/minio/admin/v3/trace",
                                  query={"trees": "1"})
        assert st == 200
        recs = json.loads(data)["traces"]
        put = next(r for r in recs if r["name"] == "api.PutObject")
        assert put["spans"] and "cpu_ms" in put and "self_wait_ms" in put
        lane = next(r for r in recs if r["name"] == "lane.dispatch")
        assert lane["tags"]["program"] == "p"
        assert lane["tags"]["targets"] == 2 and lane["tags"]["members"]
    # tools/trace_dump.py --ring is the route's reader.
    spec = importlib.util.spec_from_file_location(
        "trace_dump", os.path.join(os.path.dirname(BENCH), "tools",
                                   "trace_dump.py"))
    dump = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(dump)
    args = ["--endpoint", srv.endpoint, "--access-key", ACCESS,
            "--secret-key", SECRET, "--ring"]
    assert dump.main(args + ["--json"]) == 0
    names = [json.loads(ln)["name"] for ln in
             capsys.readouterr().out.splitlines()]
    assert "api.PutObject" in names and "lane.dispatch" in names
    assert dump.main(args) == 0
    shown = capsys.readouterr().out
    assert "engine.encode" in shown and "  cpu " in shown
    st, _, data = cli.request("GET", "/minio/admin/v3/trace")
    apis = [f["api"] for f in json.loads(data)["trace"]]
    assert "api.PutObject" in apis and "api.PutBucket" in apis
