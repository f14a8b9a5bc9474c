"""Observability + admin API tests: metrics, trace, health, logging,
admin endpoints over signed HTTP."""

import http.client
import json

import pytest

from minio_tpu.background.scanner import DataScanner
from minio_tpu.engine.pools import ServerPools
from minio_tpu.engine.sets import ErasureSets
from minio_tpu.iam.iam import IAMSys
from minio_tpu.observe.logger import Logger, RingTarget, audit_entry
from minio_tpu.observe.metrics import MetricsRegistry
from minio_tpu.observe import span as ospan
from minio_tpu.server.client import S3Client, S3ClientError
from minio_tpu.server.server import S3Server
from minio_tpu.server.sigv4 import Credentials
from minio_tpu.storage.drive import LocalDrive

ROOT, SECRET = "obsadmin", "obsadmin-secret"


@pytest.fixture()
def stack(tmp_path):
    drives = [LocalDrive(str(tmp_path / f"d{i}")) for i in range(4)]
    pools = ServerPools([ErasureSets(drives, set_drive_count=4)])
    scanner = DataScanner(pools)
    iam = IAMSys(pools)
    srv = S3Server(pools, Credentials(ROOT, SECRET), iam=iam,
                   scanner=scanner).start()
    cli = S3Client(srv.endpoint, ROOT, SECRET)
    yield srv, cli, scanner
    srv.shutdown()


def http_get(srv, path):
    conn = http.client.HTTPConnection(srv.host, srv.port, timeout=10)
    conn.request("GET", path)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


class TestUnits:
    def test_metrics_render(self):
        m = MetricsRegistry()
        m.observe_request("GET", 200, 0.004, 100, 5000)
        m.observe_request("PUT", 500, 0.2, 1000, 0)
        text = m.render()
        assert 'mtpu_s3_requests_total{api="GET",status="200"} 1' in text
        assert 'mtpu_s3_errors_total{code="500"} 1' in text
        assert "mtpu_s3_ttfb_seconds_count 2" in text

    def test_tracer_zero_cost_without_subscribers(self):
        """The one trace plane: no subscriber, no record (and no Span);
        a subscriber gets each finished request root, whose flat form
        is the line the polling admin route serves."""
        tr = ospan.SpanTracer()
        tr.configure(ring=0, sample=1.0)
        assert not tr.enabled
        assert tr.root("api.GetObject", method="GET", path="/x") \
            is ospan.NOOP
        q = tr.subscribe()
        with tr.root("api.PutObject", method="PUT", path="/y") as sp:
            sp.tag(status=200, request_size=7, response_size=0,
                   source_ip="1.2.3.4")
        assert len(q) == 1
        line = ospan.flat(q[0])
        assert line["method"] == "PUT" and line["path"] == "/y"
        assert line["api"] == "api.PutObject"
        assert line["statusCode"] == 200 and line["requestSize"] == 7
        assert line["sourceIp"] == "1.2.3.4" and line["durationMs"] >= 0
        tr.unsubscribe(q)
        assert tr.root("api.GetObject", method="GET", path="/z") \
            is ospan.NOOP
        assert len(q) == 1

    def test_logger_ring_and_once(self):
        log = Logger()
        log.targets = []                       # silence console
        ring = RingTarget(size=3)
        log.add_target(ring)
        for i in range(5):
            log.info(f"msg{i}")
        assert [e["message"] for e in ring.tail()] == \
            ["msg2", "msg3", "msg4"]
        log.log_once("error", "dup", key="k1")
        log.log_once("error", "dup", key="k1")
        assert sum(1 for e in ring.tail() if e["message"] == "dup") == 1

    def test_audit_entry_shape(self):
        e = audit_entry(method="PUT", path="/b/k", status=200,
                        duration_ms=3.2, access_key="ak",
                        source_ip="1.2.3.4")
        assert e["api"]["statusCode"] == 200
        assert e["remoteHost"] == "1.2.3.4"


class TestEndpoints:
    def test_health_live_and_cluster(self, stack):
        srv, cli, _ = stack
        status, _ = http_get(srv, "/minio/health/live")
        assert status == 200
        status, data = http_get(srv, "/minio/health/cluster")
        assert status == 200
        detail = json.loads(data)
        assert detail["sets"][0]["online"] == 4
        # kill 2 drives -> below write quorum (3 of 4) -> 503
        es = srv.pools.pools[0].sets[0]
        saved = list(es.drives)
        es.drives[0] = es.drives[1] = None
        status, _ = http_get(srv, "/minio/health/cluster")
        assert status == 503
        es.drives = saved

    def test_prometheus_metrics_endpoint(self, stack):
        srv, cli, _ = stack
        cli.make_bucket("mtr")
        cli.put_object("mtr", "k", b"x" * 1000)
        status, data = http_get(srv, "/minio/v2/metrics/cluster")
        assert status == 200
        text = data.decode()
        assert "mtpu_s3_requests_total" in text
        assert "mtpu_cluster_drives_online 4" in text

    def test_trace_captures_requests(self, stack):
        srv, cli, _ = stack
        # subscribe via admin trace endpoint (first call registers)
        cli.request("GET", "/minio/admin/v1/trace")
        cli.make_bucket("trc")
        cli.put_object("trc", "k", b"y")
        status, _, data = cli.request("GET", "/minio/admin/v1/trace")
        assert status == 200
        trace = json.loads(data)["trace"]
        assert any(t["method"] == "PUT" and "/trc/k" in t["path"]
                   for t in trace)


class TestAdminAPI:
    def test_info_and_usage(self, stack):
        srv, cli, scanner = stack
        cli.make_bucket("adm")
        cli.put_object("adm", "k", b"z" * 2000)
        status, _, data = cli.request("GET", "/minio/admin/v1/info")
        assert status == 200
        info = json.loads(data)
        assert info["mode"] == "online" and info["buckets"]["count"] == 1
        status, _, data = cli.request("GET", "/minio/admin/v1/datausage")
        assert status == 200
        usage = json.loads(data)
        assert usage["buckets"]["adm"]["b"] == 2000

    def test_admin_requires_root(self, stack):
        srv, cli, _ = stack
        srv.iam.add_user("peon", "peon-secret-123", ["readwrite"])
        peon = S3Client(srv.endpoint, "peon", "peon-secret-123")
        status, _, data = peon.request("GET", "/minio/admin/v1/info")
        assert status == 403

    def test_heal_sequence_via_admin(self, stack):
        import time
        srv, cli, _ = stack
        cli.make_bucket("healb")
        cli.put_object("healb", "obj", b"h" * 200000)
        import os, shutil
        es = srv.pools.pools[0].sets[0]
        shutil.rmtree(os.path.join(es.drives[2].root, "healb"))
        status, _, data = cli.request("POST", "/minio/admin/v1/heal",
                                      query={"bucket": "healb"})
        assert status == 200
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            _, _, data = cli.request("GET", "/minio/admin/v1/heal")
            seqs = json.loads(data)["sequences"]
            if seqs and seqs[0]["state"] in ("done", "failed"):
                break
            time.sleep(0.2)
        assert seqs[0]["state"] == "done"
        assert seqs[0]["healed"] == 1

    def test_user_management(self, stack):
        srv, cli, _ = stack
        body = json.dumps({"accessKey": "adminmade",
                           "secretKey": "adminmade-secret",
                           "policies": ["readonly"]}).encode()
        status, _, _ = cli.request("POST", "/minio/admin/v1/users",
                                   body=body)
        assert status == 200
        _, _, data = cli.request("GET", "/minio/admin/v1/users")
        assert "adminmade" in json.loads(data)["users"]
        made = S3Client(srv.endpoint, "adminmade", "adminmade-secret")
        assert isinstance(made.list_buckets(), list)
        status, _, _ = cli.request("DELETE", "/minio/admin/v1/users",
                                   query={"accessKey": "adminmade"})
        assert status == 200
        with pytest.raises(S3ClientError):
            made.list_buckets()

    def test_console_log_endpoint(self, stack):
        srv, cli, _ = stack
        srv.log.info("hello from test", component="t")
        status, _, data = cli.request("GET", "/minio/admin/v1/console")
        assert status == 200
        msgs = [e["message"] for e in json.loads(data)["log"]]
        assert "hello from test" in msgs


class TestAdminBreadth:
    """Round-3 admin surface: non-root admins, groups CRUD, policy CRUD,
    madmin-shaped info, real service semantics (VERDICT r2 item 7)."""

    def test_non_root_admin_via_policy(self, stack):
        srv, cli, _ = stack
        import json
        srv.iam.set_policy("ops-admin", {"Statement": [
            {"Effect": "Allow",
             "Action": ["admin:ServerInfo", "admin:ListUsers"],
             "Resource": "*"}]})
        srv.iam.add_user("opsuser", "opsuser-secret1", ["ops-admin"])
        ops = S3Client(srv.endpoint, "opsuser", "opsuser-secret1")
        status, _, data = ops.request("GET", "/minio/admin/v1/info")
        assert status == 200
        assert json.loads(data)["backend"]["backendType"] == "Erasure"
        status, _, _ = ops.request("GET", "/minio/admin/v1/users")
        assert status == 200
        # not granted: user creation and service control
        status, _, _ = ops.request(
            "POST", "/minio/admin/v1/users",
            body=json.dumps({"accessKey": "x", "secretKey": "x" * 12}
                            ).encode())
        assert status == 403
        status, _, _ = ops.request("POST", "/minio/admin/v1/service",
                                   query={"action": "restart"})
        assert status == 403

    def test_group_crud_endpoints(self, stack):
        srv, cli, _ = stack
        import json
        srv.iam.add_user("gmember", "gmember-secret1", [])
        body = json.dumps({"name": "readers", "members": ["gmember"],
                           "policies": ["readonly"]}).encode()
        status, _, _ = cli.request("POST", "/minio/admin/v1/groups",
                                   body=body)
        assert status == 200
        _, _, data = cli.request("GET", "/minio/admin/v1/groups")
        assert "readers" in json.loads(data)["groups"]
        _, _, data = cli.request("GET", "/minio/admin/v1/groups",
                                 query={"name": "readers"})
        info = json.loads(data)
        assert info["members"] == ["gmember"]
        assert info["policies"] == ["readonly"]
        # membership grants the group's policy
        ident = srv.iam.lookup("gmember")
        assert srv.iam.is_allowed(ident, "s3:GetObject", "any/k")
        # non-empty delete refused; empty delete works
        status, _, _ = cli.request("DELETE", "/minio/admin/v1/groups",
                                   query={"name": "readers"})
        assert status == 409
        cli.request("POST", "/minio/admin/v1/groups", body=json.dumps(
            {"name": "readers", "removeMembers": ["gmember"]}).encode())
        status, _, _ = cli.request("DELETE", "/minio/admin/v1/groups",
                                   query={"name": "readers"})
        assert status == 200

    def test_policy_crud_endpoints(self, stack):
        srv, cli, _ = stack
        import json
        doc = {"Statement": [{"Effect": "Allow", "Action": "s3:GetObject",
                              "Resource": "arn:aws:s3:::pub/*"}]}
        cli.request("POST", "/minio/admin/v1/policies", body=json.dumps(
            {"name": "pub-read", "policy": doc}).encode())
        _, _, data = cli.request("GET", "/minio/admin/v1/policies")
        assert "pub-read" in json.loads(data)["policies"]
        _, _, data = cli.request("GET", "/minio/admin/v1/policies",
                                 query={"name": "pub-read"})
        assert json.loads(data)["policy"]["Statement"][0]["Action"] \
            == "s3:GetObject"
        status, _, _ = cli.request("DELETE", "/minio/admin/v1/policies",
                                   query={"name": "pub-read"})
        assert status == 200
        status, _, _ = cli.request("GET", "/minio/admin/v1/policies",
                                   query={"name": "pub-read"})
        assert status == 404

    def test_service_restart_shuts_listener(self, tmp_path):
        import json
        import time
        drives = [LocalDrive(str(tmp_path / f"svc{i}")) for i in range(4)]
        pools = ServerPools([ErasureSets(drives, set_drive_count=4)])
        srv = S3Server(pools, Credentials(ROOT, SECRET)).start()
        cli = S3Client(srv.endpoint, ROOT, SECRET)
        status, _, data = cli.request("POST", "/minio/admin/v1/service",
                                      query={"action": "restart"})
        assert status == 200 and json.loads(data)["acknowledged"]
        assert srv.service_event == "restart"
        # the listener actually goes down (the CLI loop would rebuild)
        deadline = time.time() + 5
        down = False
        while time.time() < deadline:
            try:
                cli.list_buckets()
                time.sleep(0.1)
            except Exception:  # noqa: BLE001
                down = True
                break
        assert down, "listener still serving after restart request"


class TestAdminTierInspect:
    def test_tier_admin_endpoints(self, stack, tmp_path):
        import json
        srv, cli, _ = stack
        # wire a tier manager into the handlers for this server
        from minio_tpu.bucket.tier import TierManager
        srv.handlers.tier_mgr = TierManager(srv.pools)
        st, _, _ = cli.request("POST", "/minio/admin/v1/tier",
                               body=json.dumps({
                                   "name": "warm", "type": "fs",
                                   "path": str(tmp_path / "warm")}).encode())
        assert st == 200
        st, _, data = cli.request("GET", "/minio/admin/v1/tier")
        assert st == 200 and "WARM" in json.loads(data)["tiers"]

    def test_inspect_endpoint(self, stack):
        import json
        srv, cli, _ = stack
        cli.make_bucket("insp2")
        cli.put_object("insp2", "obj", b"inspect me" * 100)
        st, _, data = cli.request("GET", "/minio/admin/v1/inspect",
                                  query={"volume": "insp2",
                                         "file": "obj"})
        assert st == 200, data
        out = json.loads(data)
        assert len(out["copies"]) == 4
        raw = bytes.fromhex(out["copies"][0]["xl_meta_hex"])
        from minio_tpu.storage.xlmeta import XLMeta
        assert XLMeta.from_bytes(raw).versions


class TestAdminBreadthR4:
    """VERDICT r3 #6: error registry >=280 + KMS/bandwidth/pools/
    site-replication admin routes."""

    def test_error_registry_breadth(self):
        from minio_tpu.server.api_errors import ERRORS
        assert len(ERRORS) >= 280, len(ERRORS)
        for code, e in ERRORS.items():
            assert e.code == code
            assert 200 <= e.http_status <= 599, (code, e.http_status)
            assert e.message, code
        # spot-check statuses on well-known codes
        assert ERRORS["NoSuchKey"].http_status == 404
        assert ERRORS["SlowDown"].http_status == 503
        assert ERRORS["NotImplemented"].http_status == 501
        assert ERRORS["InvalidRange"].http_status == 416
        assert ERRORS["MissingContentLength"].http_status == 411
        # SQL/select family landed
        assert "CastFailed" in ERRORS and "LexerInvalidChar" in ERRORS

    def test_kms_admin_routes(self, tmp_path):
        from minio_tpu.crypto.kms import StaticKMS
        drives = [LocalDrive(str(tmp_path / f"k{i}")) for i in range(4)]
        pools = ServerPools([ErasureSets(drives, set_drive_count=4)])
        kms = StaticKMS(master_key=b"\x22" * 32)
        srv = S3Server(pools, Credentials(ROOT, SECRET),
                       kms=kms).start()
        cli = S3Client(srv.endpoint, ROOT, SECRET)
        try:
            st, _, body = cli.request("GET", "/minio/admin/v3/kms/status")
            assert st == 200 and b"StaticKMS" in body
            st, _, _ = cli.request("POST", "/minio/admin/v3/kms/key/create",
                                   query={"key-id": "tenant-a"})
            assert st == 200
            st, _, body = cli.request("GET", "/minio/admin/v3/kms/key/list")
            assert st == 200
            assert "tenant-a" in json.loads(body)["keys"]
            st, _, body = cli.request("GET", "/minio/admin/v3/kms/key/status",
                                      query={"key-id": "tenant-a"})
            assert st == 200
            ks = json.loads(body)
            assert ks["encryptionErr"] == "" and ks["decryptionErr"] == ""
            # derived keys actually seal/unseal distinctly
            _, pk1, sealed1 = kms.generate_data_key(b"c", key_id="tenant-a")
            assert kms.decrypt_data_key("tenant-a", sealed1, b"c") == pk1
            from minio_tpu.crypto.kms import KMSError
            with pytest.raises(KMSError):
                kms.decrypt_data_key("tenant-b", sealed1, b"c")
        finally:
            srv.shutdown()

    def test_bandwidth_monitor_route(self, stack):
        srv, cli, _ = stack
        cli.make_bucket("bwb")
        for i in range(4):
            cli.put_object("bwb", f"o{i}", b"z" * 100_000)
        st, _, body = cli.request("GET", "/minio/admin/v3/bandwidth")
        assert st == 200
        rep = json.loads(body)
        assert "bwb" in rep["buckets"]
        assert rep["buckets"]["bwb"]["rx_bytes_per_s"] > 0
        # filter by bucket list
        st, _, body = cli.request("GET", "/minio/admin/v3/bandwidth",
                                  query={"buckets": "nope"})
        assert json.loads(body)["buckets"] == {}

    def test_pools_status_route(self, stack):
        srv, cli, _ = stack
        st, _, body = cli.request("GET", "/minio/admin/v3/pools")
        assert st == 200
        pools = json.loads(body)["pools"]
        assert len(pools) == 1
        assert pools[0]["drivesTotal"] == 4
        assert pools[0]["drivesOnline"] == 4
        assert pools[0]["drivesPerSet"] == 4

    def test_site_replication_info_route(self, tmp_path):
        from minio_tpu.cluster.site_replication import (SitePeer,
                                                        SiteReplicator)
        drives = [LocalDrive(str(tmp_path / f"sr{i}")) for i in range(4)]
        pools = ServerPools([ErasureSets(drives, set_drive_count=4)])
        iam = IAMSys(pools)
        sr = SiteReplicator(iam, None, [SitePeer(
            "site-b", "http://127.0.0.1:1", "ak", "sk")])
        srv = S3Server(pools, Credentials(ROOT, SECRET), iam=iam,
                       site_replicator=sr).start()
        cli = S3Client(srv.endpoint, ROOT, SECRET)
        try:
            st, _, body = cli.request(
                "GET", "/minio/admin/v3/site-replication")
            assert st == 200
            info = json.loads(body)
            assert info["enabled"] and \
                info["sites"][0]["name"] == "site-b"
        finally:
            srv.shutdown()
        # and disabled when not configured
        srv2 = S3Server(pools, Credentials(ROOT, SECRET)).start()
        cli2 = S3Client(srv2.endpoint, ROOT, SECRET)
        try:
            st, _, body = cli2.request(
                "GET", "/minio/admin/v3/site-replication")
            assert st == 200 and not json.loads(body)["enabled"]
        finally:
            srv2.shutdown()


class TestLastMinute:
    """Sliding-window SLO tracker units (observe/lastminute.py) with an
    injected clock — no sleeps, fully deterministic."""

    def test_window_slides(self):
        from minio_tpu.observe.lastminute import ApiWindow
        now = [1000.0]
        w = ApiWindow(window_s=60, clock=lambda: now[0])
        for _ in range(10):
            w.observe("api.GetObject", 0.002)
        snap = w.snapshot()["api.GetObject"]
        assert snap["count"] == 10 and snap["errors"] == 0
        now[0] += 30
        w.observe("api.GetObject", 0.002, error=True)
        snap = w.snapshot()["api.GetObject"]
        assert snap["count"] == 11 and snap["errors"] == 1
        now[0] += 45                    # first burst ages out
        snap = w.snapshot()["api.GetObject"]
        assert snap["count"] == 1 and snap["errors"] == 1
        now[0] += 120                   # everything ages out
        # The row survives at zero (so exported gauges fall to 0
        # instead of freezing at their last value).
        snap = w.snapshot()["api.GetObject"]
        assert snap["count"] == 0 and snap["errors"] == 0

    def test_percentiles_from_buckets(self):
        from minio_tpu.observe.lastminute import ApiWindow
        now = [0.0]
        w = ApiWindow(window_s=60, clock=lambda: now[0])
        for _ in range(95):
            w.observe("api.X", 0.001)          # ~1 ms
        for _ in range(5):
            w.observe("api.X", 0.400)          # ~400 ms tail
        snap = w.snapshot()["api.X"]
        assert snap["p50_ms"] <= 2.5
        assert snap["p99_ms"] >= 250
        assert snap["count"] == 100

    def test_bytes_and_avg(self):
        from minio_tpu.observe.lastminute import ApiWindow
        now = [0.0]
        w = ApiWindow(window_s=60, clock=lambda: now[0])
        w.observe("api.PutObject", 0.010, nbytes=1000)
        w.observe("api.PutObject", 0.030, nbytes=3000)
        snap = w.snapshot()["api.PutObject"]
        assert snap["bytes"] == 4000
        assert 15 <= snap["avg_ms"] <= 25

    def test_registry_exports_window(self):
        m = MetricsRegistry()
        m.observe_api("api.GetObject", 0.005)
        m.observe_api("api.GetObject", 0.005, error=True)
        text = m.render()
        assert 'mtpu_api_last_minute_count{api="api.GetObject"} 2' \
            in text
        assert 'mtpu_api_last_minute_errors{api="api.GetObject"} 1' \
            in text
        assert 'mtpu_api_last_minute_p99{api="api.GetObject"}' in text


class TestPromMerge:
    """merge_prom / label_sample units — the cluster-aggregate text
    merge (cmd/metrics-v2.go peer merge role)."""

    def test_label_sample(self):
        from minio_tpu.observe.metrics import label_sample
        assert label_sample("mtpu_x 1", "node", "n:1") == \
            'mtpu_x{node="n:1"} 1'
        assert label_sample('mtpu_x{api="GET"} 2', "node", "n:1") == \
            'mtpu_x{api="GET",node="n:1"} 2'

    def test_merge_adds_node_label_and_dedups_meta(self):
        from minio_tpu.observe.metrics import merge_prom
        a = ("# HELP mtpu_up help\n# TYPE mtpu_up gauge\n"
             "mtpu_up 1\n")
        b = ("# HELP mtpu_up help\n# TYPE mtpu_up gauge\n"
             "mtpu_up 0\n")
        text = merge_prom([("n1", a), ("n2", b)])
        assert text.count("# HELP mtpu_up") == 1
        assert 'mtpu_up{node="n1"} 1' in text
        assert 'mtpu_up{node="n2"} 0' in text


class TestMetricsSelfTest:
    def test_registry_self_test_passes(self):
        """Every exported family is helped, namespaced, and documented
        in the README — the boot-time drift guard must hold on HEAD."""
        from minio_tpu.ops.selftest import metrics_registry_self_test
        metrics_registry_self_test()

    def test_startup_self_tests_include_registry(self):
        from minio_tpu.ops import selftest
        import inspect
        src = inspect.getsource(selftest.run_startup_self_tests)
        assert "metrics_registry_self_test" in src


class TestAdminObsEndpoints:
    """Cluster metrics + healthinfo on a standalone server: the
    fan-out degenerates to the local node."""

    def test_metrics_cluster_single_node(self, stack):
        srv, cli, _ = stack
        cli.make_bucket("obsc")
        cli.put_object("obsc", "o", b"x" * 512)
        st, _, body = cli.request("GET",
                                  "/minio/admin/v3/metrics/cluster")
        assert st == 200
        text = body.decode()
        me = f"{srv.host}:{srv.port}"
        assert f'mtpu_node_up{{node="{me}"}} 1' in text
        assert f'node="{me}"' in text
        assert "mtpu_s3_requests_total" in text

    def test_healthinfo_single_node(self, stack):
        srv, cli, _ = stack
        st, _, body = cli.request("GET", "/minio/admin/v3/healthinfo")
        assert st == 200
        hi = json.loads(body)
        me = f"{srv.host}:{srv.port}"
        assert hi["node_up"] == {me: 1}
        doc = hi["nodes"][me]
        assert len(doc["drives"]) == 4
        assert all(d["state"] == "ok" for d in doc["drives"])
        assert doc["draining"] is False
        assert doc["pools"] and doc["pools"][0]["total"] > 0

    def test_obs_admin_requires_auth(self, stack):
        srv, cli, _ = stack
        bad = S3Client(srv.endpoint, ROOT, "not-the-secret")
        st, _, _ = bad.request("GET",
                               "/minio/admin/v3/metrics/cluster")
        assert st == 403
        st, _, _ = bad.request("GET", "/minio/admin/v3/healthinfo")
        assert st == 403
