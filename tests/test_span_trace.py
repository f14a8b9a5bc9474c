"""Request-scoped span tracing + admin trace/listen streaming plane.

Covers the observe.span subsystem (zero-allocation disabled path, ring
retention, filters, PUT/GET span-tree coverage), the admin NDJSON trace
stream and top/apis aggregates, ListenNotification event streams, and
UploadPartCopy — plus the tracing-off overhead smoke guard.
"""

import hashlib
import json
import threading
import time
import xml.etree.ElementTree as ET

import numpy as np
import pytest

from minio_tpu.bucket.notify import NotificationSystem
from minio_tpu.engine.erasure_set import ErasureSet
from minio_tpu.engine.pools import ServerPools
from minio_tpu.engine.sets import ErasureSets
from minio_tpu.observe import span as ospan
from minio_tpu.server.client import S3Client, S3ClientError
from minio_tpu.server.server import S3Server
from minio_tpu.server.sigv4 import Credentials
from minio_tpu.storage.drive import LocalDrive

ACCESS, SECRET = "spanadmin", "spanadmin-secret"
NS = "{http://s3.amazonaws.com/doc/2006-03-01/}"


@pytest.fixture(autouse=True)
def tracer_reset():
    """TRACER is process-global: leave every test with tracing off."""
    yield
    ospan.TRACER.configure(ring=0, sample=1.0)
    ospan.TRACER.reset()


@pytest.fixture()
def es(tmp_path):
    drives = [LocalDrive(str(tmp_path / f"d{i}")) for i in range(4)]
    es = ErasureSet(drives)
    es.make_bucket("b")
    return es


@pytest.fixture()
def stack(tmp_path):
    drives = [LocalDrive(str(tmp_path / f"d{i}")) for i in range(4)]
    pools = ServerPools([ErasureSets(drives, set_drive_count=4)])
    srv = S3Server(pools, Credentials(ACCESS, SECRET),
                   notify=NotificationSystem()).start()
    cli = S3Client(srv.endpoint, ACCESS, SECRET)
    yield srv, cli
    srv.shutdown()


def payload(size, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()


class TestSpanUnits:
    def test_disabled_path_allocates_no_spans(self, es):
        """Tracing off: root() returns the NOOP singleton and a full
        engine GET materialises zero Span objects (SPAN_ALLOCS is the
        allocation sentinel incremented by Span.__init__)."""
        es.put_object("b", "o", payload(1 << 20))
        before = ospan.SPAN_ALLOCS
        assert ospan.TRACER.root("api.GetObject") is ospan.NOOP
        with ospan.span("engine.nothing"):
            pass
        ospan.record("engine.nothing", 0.001)
        _, got = es.get_object("b", "o")
        assert len(got) == 1 << 20
        assert ospan.SPAN_ALLOCS == before

    def test_ring_keeps_newest_n(self):
        ospan.TRACER.configure(ring=3, sample=1.0)
        for i in range(7):
            with ospan.TRACER.root(f"api.Op{i}"):
                pass
        names = [r["name"] for r in ospan.TRACER.traces()]
        assert names == ["api.Op4", "api.Op5", "api.Op6"]

    def test_ring_resize_preserves_existing(self):
        ospan.TRACER.configure(ring=4, sample=1.0)
        with ospan.TRACER.root("api.Keep"):
            pass
        ospan.TRACER.configure(ring=8, sample=1.0)
        assert [r["name"] for r in ospan.TRACER.traces()] == ["api.Keep"]

    def test_filter_model(self):
        rec_ok = {"name": "api.GetObject", "dur_ms": 5.0, "error": False,
                  "tags": {"path": "/b/x"}}
        rec_err = {"name": "api.GetObject", "dur_ms": 0.2, "error": True,
                   "tags": {"path": "/other/y"}}
        f = ospan.TraceFilter.from_query(
            {"err": "true", "path": "/b", "min-duration-ms": "1"})
        assert not f.matches(rec_ok)      # not an error
        assert not f.matches(rec_err)     # wrong prefix + too fast
        assert ospan.TraceFilter.from_query({}).matches(rec_ok)
        assert ospan.TraceFilter(err_only=True).matches(rec_err)
        assert not ospan.TraceFilter(min_ms=1.0).matches(rec_err)
        assert ospan.TraceFilter(path_prefix="/b").matches(rec_ok)

    def test_subscriber_alone_enables_tracing(self):
        assert not ospan.TRACER.enabled
        q = ospan.TRACER.subscribe()
        try:
            assert ospan.TRACER.enabled
            with ospan.TRACER.root("api.X", path="/p"):
                with ospan.span("stage.one"):
                    pass
            assert len(q) == 1
            assert q[0]["spans"][0]["name"] == "stage.one"
        finally:
            ospan.TRACER.unsubscribe(q)
        assert not ospan.TRACER.enabled

    def test_put_get_trace_coverage(self, es):
        """A traced 16 MiB PUT and GET each yield >= 5 distinct named
        child spans summing to >= 80% of the root wall time."""
        data = payload(16 << 20, seed=9)
        es.put_object("b", "big", data)          # warm (compile, cache)
        es.get_object("b", "big")
        ospan.TRACER.configure(ring=8, sample=1.0)
        with ospan.TRACER.root("api.PutObject", path="/b/big"):
            es.put_object("b", "big", data)
        with ospan.TRACER.root("api.GetObject", path="/b/big"):
            _, got = es.get_object("b", "big")
        assert bytes(got) == data
        put_rec, get_rec = ospan.TRACER.traces()[-2:]
        for rec in (put_rec, get_rec):
            stages = ospan.flatten(rec)
            assert len(stages) >= 5, stages
            assert ospan.coverage(rec) >= 0.8, (rec["name"],
                                                rec["dur_ms"], stages)

    def test_aggregates_snapshot(self, es):
        ospan.TRACER.configure(ring=4, sample=1.0)
        for _ in range(3):
            with ospan.TRACER.root("api.PutObject", path="/b/agg"):
                es.put_object("b", "agg", payload(1 << 20))
        snap = ospan.TRACER.snapshot()
        api = snap["apis"]["api.PutObject"]
        assert api["count"] == 3 and api["errors"] == 0
        assert api["p50_ms"] > 0 and api["avg_ms"] > 0
        assert "engine.encode" in api["stages"]
        enc = api["stages"]["engine.encode"]
        assert enc["count"] >= 3
        assert sum(enc["buckets"]) == enc["count"]

    def test_span_metrics_exported(self, es):
        from minio_tpu.observe.metrics import MetricsRegistry
        ospan.TRACER.configure(ring=4, sample=1.0)
        with ospan.TRACER.root("api.PutObject", path="/b/m"):
            es.put_object("b", "m", payload(1 << 20))
        text = MetricsRegistry().render()
        assert 'mtpu_trace_api_requests_total{api="api.PutObject"} 1' \
            in text
        assert 'mtpu_trace_stage_duration_ms_bucket{api="api.PutObject"' \
            in text and 'le="+Inf"' in text


class TestAdminTraceEndpoints:
    def _collect(self, cli, query, out):
        st, _, body = cli.request("POST", "/minio/admin/v3/trace",
                                  query=query)
        out.append((st, body))

    def test_trace_stream_delivers_request(self, stack):
        srv, cli = stack
        cli.make_bucket("tbk")
        out = []
        t = threading.Thread(target=self._collect, args=(
            cli, {"duration": "2"}, out))
        t.start()
        # Wait for the stream subscription to flip TRACER.enabled.
        deadline = time.monotonic() + 5
        while not ospan.TRACER.enabled and time.monotonic() < deadline:
            time.sleep(0.02)
        assert ospan.TRACER.enabled
        cli.put_object("tbk", "hello", payload(1 << 20))
        t.join(timeout=15)
        assert out and out[0][0] == 200
        recs = [json.loads(line) for line in out[0][1].splitlines()
                if line.strip()]
        puts = [r for r in recs if r["name"] == "api.PutObject"]
        assert puts, recs
        rec = puts[0]
        tags = rec["tags"]
        assert tags["path"] == "/tbk/hello"
        assert tags["bucket"] == "tbk" and tags["object"] == "hello"
        assert tags["status"] == 200 and not rec["error"]
        assert any(c["name"].startswith("engine.")
                   for c in rec.get("spans", []))

    def test_trace_stream_err_filter(self, stack):
        srv, cli = stack
        cli.make_bucket("tfk")
        cli.put_object("tfk", "x", b"data")
        out = []
        t = threading.Thread(target=self._collect, args=(
            cli, {"duration": "2", "err": "true"}, out))
        t.start()
        deadline = time.monotonic() + 5
        while not ospan.TRACER.enabled and time.monotonic() < deadline:
            time.sleep(0.02)
        cli.get_object("tfk", "x")                       # 200: filtered
        with pytest.raises(S3ClientError):
            cli.get_object("tfk", "missing")             # 404: streamed
        t.join(timeout=15)
        recs = [json.loads(line) for line in out[0][1].splitlines()
                if line.strip()]
        assert recs and all(r["error"] for r in recs)
        assert any(r["tags"]["path"] == "/tfk/missing" for r in recs)

    def test_top_apis_route(self, stack):
        srv, cli = stack
        ospan.TRACER.configure(ring=16, sample=1.0)
        cli.make_bucket("tak")
        cli.put_object("tak", "o", payload(1 << 20))
        cli.get_object("tak", "o")
        # The root span commits after the response bytes are written, so
        # the aggregate can land just after the client returns.
        deadline = time.monotonic() + 5
        while "api.GetObject" not in ospan.TRACER.snapshot()["apis"] \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        st, _, body = cli.request("GET", "/minio/admin/v3/top/apis")
        assert st == 200
        snap = json.loads(body)
        assert "api.PutObject" in snap["apis"]
        assert "api.GetObject" in snap["apis"]
        put = snap["apis"]["api.PutObject"]
        assert put["count"] >= 1 and put["stages"]
        assert snap["bucket_bounds_ms"][0] == 0.05

    def test_trace_requires_admin_auth(self, stack):
        srv, cli = stack
        bad = S3Client(srv.endpoint, "nobody", "nobody-secret")
        st, _, _ = bad.request("POST", "/minio/admin/v3/trace",
                               query={"duration": "1"})
        assert st == 403


class TestListenNotification:
    def _listen(self, cli, path, query, out):
        st, _, body = cli.request("GET", path, query=query)
        out.append((st, body))

    def test_put_during_listen_delivers_created_event(self, stack):
        srv, cli = stack
        cli.make_bucket("lbk")
        out = []
        t = threading.Thread(target=self._listen, args=(
            cli, "/lbk", {"events": "s3:ObjectCreated:*",
                         "duration": "2"}, out))
        t.start()
        notify = srv.handlers.notify
        deadline = time.monotonic() + 5
        while not notify.pubsub.num_subscribers \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert notify.pubsub.num_subscribers
        cli.put_object("lbk", "dir/new.bin", b"event payload")
        t.join(timeout=15)
        assert out and out[0][0] == 200
        lines = [json.loads(line) for line in out[0][1].splitlines()
                 if line.strip()]
        recs = [r["Records"][0] for r in lines if "Records" in r]
        assert recs, out[0][1]
        ev = recs[0]
        assert ev["eventName"] == "s3:ObjectCreated:Put"
        assert ev["s3"]["bucket"]["name"] == "lbk"
        assert ev["s3"]["object"]["key"] == "dir/new.bin"

    def test_listen_filters_prefix_and_event(self, stack):
        srv, cli = stack
        cli.make_bucket("lfk")
        out = []
        t = threading.Thread(target=self._listen, args=(
            cli, "/lfk", {"events": "s3:ObjectRemoved:*",
                         "prefix": "logs/", "duration": "2"}, out))
        t.start()
        notify = srv.handlers.notify
        deadline = time.monotonic() + 5
        while not notify.pubsub.num_subscribers \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        cli.put_object("lfk", "logs/a", b"x")       # wrong event type
        cli.put_object("lfk", "data/b", b"y")
        cli.delete_object("lfk", "data/b")          # wrong prefix
        cli.delete_object("lfk", "logs/a")          # the one match
        t.join(timeout=15)
        lines = [json.loads(line) for line in out[0][1].splitlines()
                 if line.strip()]
        recs = [r["Records"][0] for r in lines if "Records" in r]
        assert len(recs) == 1, recs
        assert recs[0]["eventName"].startswith("s3:ObjectRemoved:")
        assert recs[0]["s3"]["object"]["key"] == "logs/a"

    def test_global_listen_route(self, stack):
        srv, cli = stack
        cli.make_bucket("lgk")
        out = []
        t = threading.Thread(target=self._listen, args=(
            cli, "/minio/listen", {"duration": "2"}, out))
        t.start()
        notify = srv.handlers.notify
        deadline = time.monotonic() + 5
        while not notify.pubsub.num_subscribers \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        cli.put_object("lgk", "o", b"z")
        t.join(timeout=15)
        assert out and out[0][0] == 200
        lines = [json.loads(line) for line in out[0][1].splitlines()
                 if line.strip()]
        assert any(r["Records"][0]["s3"]["bucket"]["name"] == "lgk"
                   for r in lines if "Records" in r)


class TestUploadPartCopy:
    def _initiate(self, cli, bucket, key):
        _, _, body = cli.request("POST", f"/{bucket}/{key}",
                                 query={"uploads": ""})
        return ET.fromstring(body).findtext(f"{NS}UploadId")

    def _complete(self, cli, bucket, key, uid, parts):
        root = ET.Element("CompleteMultipartUpload")
        for n, etag in parts:
            p = ET.SubElement(root, "Part")
            ET.SubElement(p, "PartNumber").text = str(n)
            ET.SubElement(p, "ETag").text = etag
        st, _, body = cli.request("POST", f"/{bucket}/{key}",
                                  query={"uploadId": uid},
                                  body=ET.tostring(root))
        assert st == 200, body
        return body

    def test_copy_part_completes_byte_identical(self, stack):
        srv, cli = stack
        cli.make_bucket("src")
        cli.make_bucket("dst")
        src = payload(6 << 20, seed=3)
        tail = payload(1 << 20, seed=4)
        cli.put_object("src", "big", src)

        uid = self._initiate(cli, "dst", "out")
        st, _, body = cli.request(
            "PUT", "/dst/out",
            query={"partNumber": "1", "uploadId": uid},
            headers={"x-amz-copy-source": "/src/big"})
        assert st == 200, body
        cp = ET.fromstring(body)
        assert cp.tag == f"{NS}CopyPartResult"
        etag1 = cp.findtext(f"{NS}ETag").strip('"')
        # A copy-sourced part is byte-identical to an uploaded one:
        # same content md5, hence the same part ETag.
        assert etag1 == hashlib.md5(src).hexdigest()
        assert cp.findtext(f"{NS}LastModified")
        _, h, _ = cli.request("PUT", "/dst/out",
                              query={"partNumber": "2", "uploadId": uid},
                              body=tail)
        etag2 = h["ETag"].strip('"')
        self._complete(cli, "dst", "out", uid, [(1, etag1), (2, etag2)])
        assert cli.get_object("dst", "out") == src + tail

    def test_copy_part_with_range(self, stack):
        srv, cli = stack
        cli.make_bucket("rsrc")
        cli.make_bucket("rdst")
        src = payload(8 << 20, seed=5)
        cli.put_object("rsrc", "obj", src)
        uid = self._initiate(cli, "rdst", "out")
        lo, hi = 1 << 20, (7 << 20) - 1                # 6 MiB slice
        st, _, body = cli.request(
            "PUT", "/rdst/out",
            query={"partNumber": "1", "uploadId": uid},
            headers={"x-amz-copy-source": "/rsrc/obj",
                     "x-amz-copy-source-range": f"bytes={lo}-{hi}"})
        assert st == 200, body
        etag = ET.fromstring(body).findtext(f"{NS}ETag").strip('"')
        assert etag == hashlib.md5(src[lo:hi + 1]).hexdigest()
        self._complete(cli, "rdst", "out", uid, [(1, etag)])
        assert cli.get_object("rdst", "out") == src[lo:hi + 1]

    def test_copy_part_errors(self, stack):
        srv, cli = stack
        cli.make_bucket("esrc")
        cli.make_bucket("edst")
        cli.put_object("esrc", "obj", b"0123456789")
        uid = self._initiate(cli, "edst", "out")
        st, _, body = cli.request(
            "PUT", "/edst/out",
            query={"partNumber": "1", "uploadId": uid},
            headers={"x-amz-copy-source": "/esrc/missing"})
        assert st == 404 and b"NoSuchKey" in body
        # Range beyond the source is a hard error (unlike ranged GET).
        st, _, body = cli.request(
            "PUT", "/edst/out",
            query={"partNumber": "1", "uploadId": uid},
            headers={"x-amz-copy-source": "/esrc/obj",
                     "x-amz-copy-source-range": "bytes=5-100"})
        assert st == 416 and b"InvalidRange" in body


def _span(name, t0, dur, children=()):
    """A finished span at a made-up place on the clock."""
    sp = ospan.Span(ospan.TRACER, name)
    sp.t0, sp.dur_s = t0, dur
    sp.children = list(children)
    return sp


class TestSpanTimeline:
    def test_self_ms_overlapping_pool_children(self):
        """Children that ran side by side in pool threads are taken off
        the parent once (their union), and clipped to it."""
        kids = [_span("storage.append", 1.0, 2.0),     # [1, 3]
                _span("storage.append", 2.0, 2.0),     # [2, 4] overlaps
                _span("storage.append", 6.0, 1.0),     # [6, 7] apart
                _span("storage.append", 9.0, 5.0)]     # [9, 14] clipped
        parent = _span("mp.write", 0.0, 10.0, kids)
        assert parent.self_s() == pytest.approx(10.0 - (3.0 + 1.0 + 1.0))
        assert kids[0].self_s() == 2.0
        rec = parent.to_dict()
        assert rec["self_ms"] == pytest.approx(5000.0)
        assert [c["self_ms"] for c in rec["spans"]] == \
            [2000.0, 2000.0, 1000.0, 5000.0]

    def test_start_ms_ordering_and_record_placement(self):
        ospan.TRACER.configure(ring=4, sample=1.0)
        with ospan.TRACER.root("api.X", request_id="rid-1"):
            with ospan.span("stage.first"):
                time.sleep(0.01)
            t0 = time.monotonic()
            time.sleep(0.02)
            with ospan.span("stage.inner"):
                time.sleep(0.005)
            t1 = time.monotonic()
            ospan.bracket("stage.bracket", t0, t1)
            time.sleep(0.01)
            ospan.record("stage.recorded", 0.01)
        rec = ospan.TRACER.traces()[-1]
        assert rec["start_ms"] == 0.0
        assert rec["tags"]["request_id"] == "rid-1"
        by = {c["name"]: c for c in rec["spans"]}
        assert list(by) == ["stage.first", "stage.bracket",
                            "stage.recorded"]
        starts = [c["start_ms"] for c in rec["spans"]]
        assert starts == sorted(starts) and starts[0] >= 0.0
        # record() ends now: it began its seconds ago, after the bracket.
        assert by["stage.recorded"]["start_ms"] >= \
            by["stage.bracket"]["start_ms"] + by["stage.bracket"]["dur_ms"]
        assert by["stage.recorded"]["start_ms"] + 10.0 <= rec["dur_ms"] + 1
        # bracket() took the span that ran inside it for its child.
        inner = by["stage.bracket"]["spans"][0]
        assert inner["name"] == "stage.inner"
        assert inner["start_ms"] >= by["stage.bracket"]["start_ms"] + 19
        assert by["stage.bracket"]["self_ms"] == pytest.approx(
            by["stage.bracket"]["dur_ms"] - inner["dur_ms"], abs=0.01)

    @pytest.mark.parametrize("stage,layer", [
        ("http.auth", "front_door"), ("http.other", "front_door"),
        ("engine.frame", "engine"), ("mp.encode", "engine"),
        ("storage.append", "storage"), ("host.hash_batch", "storage"),
        ("coalesce.wait", "dispatch"), ("ipc.wait", "dispatch"),
        ("metalane.wait", "dispatch"), ("lane.h2d", "lane"),
        ("device.compile", "device"), ("heal.read", "other")])
    def test_layer_table(self, stage, layer):
        assert ospan.layer_of(stage) == layer

    def test_http_other_is_root_minus_children(self):
        """The exporter's stage http.other is the root's own self time:
        per API, the self times of every stage and http.other add up
        to the summed root duration."""
        from minio_tpu.observe.metrics import MetricsRegistry
        ospan.TRACER.configure(ring=4, sample=1.0)
        with ospan.TRACER.root("api.GetObject", method="GET"):
            time.sleep(0.01)
            with ospan.span("engine.read"):
                with ospan.span("storage.read"):
                    time.sleep(0.01)
            time.sleep(0.005)
        rec = ospan.TRACER.traces()[-1]
        api = ospan.TRACER.snapshot()["apis"]["api.GetObject"]
        kids_ms = sum(c["dur_ms"] for c in rec["spans"])
        assert api["self_ms"] == pytest.approx(rec["dur_ms"] - kids_ms,
                                               abs=0.01)
        assert api["self_ms"] >= 14.0
        total = api["self_ms"] + sum(st["self_ms"]
                                     for st in api["stages"].values())
        assert total == pytest.approx(api["total_ms"], abs=0.01)
        text = MetricsRegistry().render()
        assert ('mtpu_trace_stage_self_ms_total{api="api.GetObject",'
                'stage="http.other",layer="front_door"}') in text
        assert ('mtpu_trace_stage_self_ms_total{api="api.GetObject",'
                'stage="storage.read",layer="storage"}') in text


@pytest.fixture()
def device_path(monkeypatch):
    """The device codec on the CPU backend, through a cold coalescer."""
    from minio_tpu.engine import shardmath
    from minio_tpu.ops import coalesce
    monkeypatch.setattr(shardmath, "platform", lambda: (True, False))
    coalesce.reset()
    yield coalesce
    coalesce.reset()


class TestLaneAndCompile:
    def test_lane_dispatch_root_children_and_members(self, es,
                                                     device_path):
        """One PUT through the lane thread: the lane's own root names
        the request it served and holds the four phases; the request
        sees its block as coalesce.wait."""
        data = payload(2 << 20, seed=3)
        es.put_object("b", "warm", data)                  # compile
        device_path.get()._ema = 2.0      # traffic: queue, do not inline
        ospan.TRACER.configure(ring=16, sample=1.0)
        with ospan.TRACER.root("api.PutObject", request_id="rid-lane"):
            es.put_object("b", "o", data)
        deadline = time.monotonic() + 10
        lanes = []
        while not lanes and time.monotonic() < deadline:
            lanes = [r for r in ospan.TRACER.traces()
                     if r["name"] == "lane.dispatch"]
            time.sleep(0.01)
        assert lanes, [r["name"] for r in ospan.TRACER.traces()]
        rec = lanes[0]
        assert [c["name"] for c in rec["spans"]] == [
            "lane.pack", "lane.h2d", "lane.launch", "lane.device_wait"]
        tags = rec["tags"]
        assert tags["members"] == ["rid-lane"] and tags["device"] == 0
        assert tags["program"].startswith("enc/") and tags["items"] == 1
        assert tags["rows"] == 2 and tags["padded_rows"] == 32
        starts = [c["start_ms"] for c in rec["spans"]]
        assert starts == sorted(starts)
        put = next(r for r in ospan.TRACER.traces()
                   if r["name"] == "api.PutObject")
        waits = [v for k, v in ospan.flatten(put).items()
                 if k == "coalesce.wait"]
        assert waits and waits[0] > 0

    def test_lane_states_sum_to_lifetime(self, es, device_path):
        data = payload(2 << 20, seed=4)
        es.put_object("b", "inline", data)         # inline dispatch
        device_path.get()._ema = 2.0
        es.put_object("b", "queued", data)         # through the thread
        time.sleep(0.05)                           # parked again
        lane = device_path.get().lane(0)
        state_s = lane.state_seconds()
        age = time.monotonic() - lane.t_created
        assert set(state_s) == set(lane.STATES)
        assert sum(state_s.values()) == pytest.approx(age, rel=0.01)
        for state in ("no_work", "pack", "launch", "device_wait"):
            assert state_s[state] > 0, state_s
        from minio_tpu.observe.metrics import MetricsRegistry
        text = MetricsRegistry().render()
        for state in lane.STATES:
            assert ('mtpu_device_lane_state_seconds_total{lane="0",'
                    f'state="{state}"}}') in text

    def test_first_sight_compile_counted_and_on_tree(self):
        import jax
        import jax.numpy as jnp

        from minio_tpu.observe.metrics import DATA_PATH, MetricsRegistry
        from minio_tpu.ops import devices
        devices.n_devices()          # where the listener is registered
        salt = time.time_ns() % 1_000_003     # a program no cache holds
        x = jnp.arange(7)
        before = DATA_PATH.snapshot()["jit_compiles"]
        ospan.TRACER.configure(ring=4, sample=1.0)
        with ospan.TRACER.root("api.GetObject"):
            with ospan.span("engine.verify"):
                jax.jit(lambda v: v * 3 + salt)(x).block_until_ready()
        snap = DATA_PATH.snapshot()
        assert snap["jit_compiles"] == before + 1
        assert snap["jit_compile_s"] > 0
        rec = ospan.TRACER.traces()[-1]
        verify = rec["spans"][0]
        assert [c["name"] for c in verify["spans"]] == ["device.compile"]
        assert verify["spans"][0]["dur_ms"] > 0
        text = MetricsRegistry().render()
        assert f"mtpu_jit_compiles_total {before + 1}" in text

    def test_traced_request_makes_the_same_jax_calls(self, es,
                                                     device_path,
                                                     monkeypatch):
        """Tracing adds no sync: a traced PUT + GET calls
        jax.block_until_ready exactly as often as an untraced one."""
        import jax
        calls = []
        real = jax.block_until_ready
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: (calls.append(1), real(x))[1])
        data = payload(2 << 20, seed=5)
        es.put_object("b", "o", data)
        es.get_object("b", "o")                    # warm both programs
        del calls[:]
        es.put_object("b", "o", data)
        es.get_object("b", "o")
        untraced = len(calls)
        ospan.TRACER.configure(ring=4, sample=1.0)
        with ospan.TRACER.root("api.PutObject"):
            es.put_object("b", "o", data)
        with ospan.TRACER.root("api.GetObject"):
            es.get_object("b", "o")
        assert len(calls) - untraced == untraced


class TestHostGaps:
    def test_interval_attribution(self):
        """benchmark/host_gaps.py on made-up intervals: the deepest span
        open on each thread, equal parts between threads, the rest to
        `no span open`; the parts add up to the gaps."""
        import importlib.util
        import os
        import sys
        bench = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark")
        sys.path.insert(0, bench)
        try:
            spec = importlib.util.spec_from_file_location(
                "host_gaps", os.path.join(bench, "host_gaps.py"))
            hg = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(hg)
        finally:
            sys.path.remove(bench)
        threads = {
            "req": [(0, 10, "api.UploadPart"), (1, 4, "mp.encode"),
                    (2, 3, "http.read_body"), (6, 9, "mp.write")],
            # a suspended lane dispatch outlives the next one's pack
            "lane": [(2.5, 7, "lane.dispatch"), (2.5, 3.5, "lane.pack"),
                     (5, 8, "lane.dispatch")]}
        assert hg.deepest(threads["req"]) == [
            (0, 1, "api.UploadPart"), (1, 2, "mp.encode"),
            (2, 3, "http.read_body"), (3, 4, "mp.encode"),
            (4, 6, "api.UploadPart"), (6, 9, "mp.write"),
            (9, 10, "api.UploadPart")]
        assert hg.deepest(threads["lane"]) == [
            (2.5, 3.5, "lane.pack"), (3.5, 8, "lane.dispatch")]
        assert hg.program_gaps([(0, 1), (3, 4), (3.5, 6), (8, 9)]) == \
            [(1, 3), (6, 8)]
        gaps = [(0, 2), (2, 3), (9.5, 12)]
        got = hg.attribute(
            gaps, {t: hg.deepest(evs) for t, evs in threads.items()})
        assert got == {"api.UploadPart": 1.5, "mp.encode": 1.0,
                       "http.read_body": 0.75, "lane.pack": 0.25,
                       hg.NO_SPAN: 2.0}
        assert sum(got.values()) == sum(b - a for a, b in gaps)


class TestDisabledOverhead:
    def test_tracing_off_allocates_nothing(self, stack):
        """Tracing off: a served PUT, multipart upload and GET build no
        Span and no TraceAnnotation (both constructions are counted).
        Replaces the wall-clock overhead guard: a shared CPU cannot
        give a time (ROADMAP Design 12)."""
        srv, cli = stack
        assert not ospan.TRACER.enabled
        cli.make_bucket("off")
        data = payload(1 << 20, seed=6)
        before = (ospan.SPAN_ALLOCS, ospan.ANNOTATION_ALLOCS)
        cli.put_object("off", "o", data)
        assert cli.get_object("off", "o") == data
        st, _, body = cli.request("POST", "/off/mp", query={"uploads": ""})
        assert st == 200
        uid = ET.fromstring(body).find(f"{NS}UploadId").text
        st, hdrs, _ = cli.request(
            "PUT", "/off/mp", query={"partNumber": "1", "uploadId": uid},
            body=data)
        assert st == 200
        assert (ospan.SPAN_ALLOCS, ospan.ANNOTATION_ALLOCS) == before
        # ... and with tracing on the same traffic builds both.
        ospan.TRACER.configure(ring=4, sample=1.0)
        cli.put_object("off", "o", data)
        assert ospan.SPAN_ALLOCS > before[0]
        assert ospan.ANNOTATION_ALLOCS > before[1]
