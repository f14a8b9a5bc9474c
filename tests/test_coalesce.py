"""Cross-request dispatch coalescing: scheduler unit tests + engine
oracle equivalence.

The DispatchCoalescer's contract (ops/coalesce.py) is tested directly
with synthetic kernels — batching across concurrent submitters, FIFO
fairness across keys, oversized-item admission, bounded-queue
backpressure, error fan-out — and then end-to-end: concurrent mixed
PUT/GET/ranged-GET traffic must return byte-identical objects and
ETags under MTPU_COALESCE=1 and the =0 direct-dispatch oracle (the
`coalesce_mode` conftest fixture runs every engine test both ways).

The randomized stress matrix and the starvation guard are `slow`; a
2-client smoke keeps the coalesced path exercised in every tier-1 run.
"""

import hashlib
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from minio_tpu.engine.erasure_set import BLOCK_SIZE, ErasureSet
from minio_tpu.observe.metrics import DATA_PATH
from minio_tpu.ops import coalesce
from minio_tpu.storage.drive import LocalDrive


def make_set(tmp_path, n=4, parity=None, name="co"):
    drives = [LocalDrive(str(tmp_path / name / f"d{i}")) for i in range(n)]
    return ErasureSet(drives, default_parity=parity)


def payload(size, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def sum_kernel(calls=None, gate=None, block_first=False):
    """Synthetic kernel: per-span row sums.  Optionally blocks the
    dispatcher on its FIRST call (gate) so the test can pile more items
    into the queue deterministically, and records (key-free) call spans
    for occupancy/ordering assertions."""
    state = {"first": True}

    def kernel(stacked, spans, ctx):
        if block_first and state["first"]:
            state["first"] = False
            gate.wait(5.0)
        if calls is not None:
            calls.append(list(spans))
        return [int(stacked[lo:hi].sum()) for lo, hi in spans]

    return kernel


class TestScheduler:
    def test_idle_submit_runs_inline(self):
        """A lone submit on an idle scheduler executes on the calling
        thread — no dispatcher thread is even started (the zero-handoff
        guarantee behind the <5% single-client latency budget)."""
        co = coalesce.DispatchCoalescer()
        h = co.submit(("solo",), np.ones(3, dtype=np.uint8),
                      sum_kernel())
        assert h.result(1.0) == 3
        assert co._thread is None
        st = co.stats()
        assert st["dispatches"] == 1 and st["items"] == 1
        co.close()

    def test_batches_items_queued_during_dispatch(self):
        """Items that arrive while a dispatch is in flight pack into
        the NEXT dispatch — the continuous-batching mechanism itself,
        no window needed."""
        co = coalesce.DispatchCoalescer()
        co._ema = 2.0                 # force queued (non-inline) mode
        calls, gate = [], threading.Event()
        fn = sum_kernel(calls, gate, block_first=True)
        key = ("t", 1)
        h0 = co.submit(key, np.ones(2, dtype=np.uint8), fn)
        time.sleep(0.05)              # dispatcher is now blocked in fn
        hs = [co.submit(key, np.full(3, i, dtype=np.uint8), fn)
              for i in range(1, 4)]
        gate.set()
        assert h0.result(5.0) == 2
        assert [h.result(5.0) for h in hs] == [3, 6, 9]
        st = co.stats()
        assert st["dispatches"] == 2
        assert st["items"] == 4
        assert st["max_items"] == 3          # the packed batch
        assert len(calls[1]) == 3
        co.close()

    def test_fifo_across_keys(self):
        """The key whose head item is oldest dispatches first."""
        co = coalesce.DispatchCoalescer()
        co._ema = 2.0                 # force queued (non-inline) mode
        order = []
        gate = threading.Event()

        def mk(tag):
            def kernel(stacked, spans, ctx):
                if tag == "warm":
                    gate.wait(5.0)
                else:
                    order.append(tag)
                return [None for _ in spans]
            return kernel

        hw = co.submit(("warm",), np.zeros(1, dtype=np.uint8), mk("warm"))
        time.sleep(0.05)
        ha = co.submit(("a",), np.zeros(1, dtype=np.uint8), mk("a"))
        time.sleep(0.02)              # b's head is strictly younger
        hb = co.submit(("b",), np.zeros(1, dtype=np.uint8), mk("b"))
        gate.set()
        for h in (hw, ha, hb):
            h.result(5.0)
        assert order == ["a", "b"]
        co.close()

    def test_oversized_item_dispatches_alone(self, monkeypatch):
        monkeypatch.setenv("MTPU_COALESCE_MAX_BATCH", "4")
        co = coalesce.DispatchCoalescer()
        h = co.submit(("big",), np.ones(100, dtype=np.uint8),
                      sum_kernel(), weight=100)
        assert h.result(5.0) == 100
        st = co.stats()
        assert st["dispatches"] == 1 and st["items"] == 1
        co.close()

    def test_backpressure_bounds_queue(self, monkeypatch):
        monkeypatch.setenv("MTPU_COALESCE_MAX_BATCH", "4")   # cap = 16
        co = coalesce.DispatchCoalescer()
        co._ema = 2.0                 # force queued (non-inline) mode
        gate = threading.Event()
        fn = sum_kernel(gate=gate, block_first=True)
        key = ("bp",)
        co.submit(key, np.zeros(1, dtype=np.uint8), fn, weight=1)
        time.sleep(0.05)              # dispatcher blocked; queue empty
        co.submit(key, np.zeros(8, dtype=np.uint8), fn, weight=8)
        co.submit(key, np.zeros(8, dtype=np.uint8), fn, weight=8)
        done = threading.Event()

        def overflow():
            co.submit(key, np.zeros(8, dtype=np.uint8), fn, weight=8)
            done.set()

        t = threading.Thread(target=overflow, daemon=True)
        t.start()
        # 16 queued weight already at the cap: the third submit blocks.
        assert not done.wait(0.3)
        gate.set()                    # drain -> space frees -> admitted
        assert done.wait(5.0)
        t.join(5.0)
        assert co.stats()["pending_weight"] <= 16
        co.close()

    def test_kernel_error_fans_out(self):
        co = coalesce.DispatchCoalescer()
        co._ema = 2.0                 # force queued (non-inline) mode
        gate = threading.Event()

        def boom(stacked, spans, ctx):
            gate.wait(5.0)
            raise ValueError("kernel exploded")

        h1 = co.submit(("err",), np.zeros(1, dtype=np.uint8), boom)
        time.sleep(0.05)
        h2 = co.submit(("err",), np.zeros(1, dtype=np.uint8), boom)
        gate.set()
        for h in (h1, h2):
            with pytest.raises(ValueError, match="exploded"):
                h.result(5.0)
        co.close()

    def test_pad_batch(self):
        x = np.arange(10, dtype=np.uint8).reshape(5, 2)
        p, n = coalesce.pad_batch(x, 4)
        assert n == 5 and p.shape == (8, 2)
        assert np.array_equal(p[:5], x) and not p[5:].any()
        same, n2 = coalesce.pad_batch(x[:4], 4)
        assert n2 == 4 and same.shape == (4, 2)


POISON = 66


def picky_kernel(stacked, spans, ctx):
    """Sums spans but refuses any span containing the POISON byte —
    so a packed batch fails wholesale, and the per-member retry can
    isolate exactly the guilty span."""
    out = []
    for lo, hi in spans:
        if (stacked[lo:hi] == POISON).any():
            raise ValueError("poisoned span")
        out.append(int(stacked[lo:hi].sum()))
    return out


class TestFaultContainment:
    def test_poisoned_member_fails_only_itself(self):
        """One bad item in a packed batch: the batch dispatch faults,
        the per-member retry resolves the innocent neighbors with
        results and pins the exception on the guilty handle alone."""
        co = coalesce.DispatchCoalescer()
        co._ema = 2.0                 # force queued (non-inline) mode
        gate = threading.Event()
        warm = sum_kernel(gate=gate, block_first=True)
        key = ("fc", 1)
        h0 = co.submit(key, np.ones(1, dtype=np.uint8), warm)
        time.sleep(0.05)              # dispatcher blocked: pile up a batch
        good1 = co.submit(key, np.full(2, 3, dtype=np.uint8),
                          picky_kernel)
        bad = co.submit(key, np.full(2, POISON, dtype=np.uint8),
                        picky_kernel)
        good2 = co.submit(key, np.full(4, 2, dtype=np.uint8),
                          picky_kernel)
        gate.set()
        assert h0.result(5.0) == 1
        assert good1.result(5.0) == 6
        assert good2.result(5.0) == 8
        with pytest.raises(ValueError, match="poisoned"):
            bad.result(5.0)
        st = co.stats()
        assert st["batch_faults"] == 1
        assert st["member_retries"] == 3
        assert not st["broken"]
        # the scheduler survives: later work still dispatches
        assert co.submit(key, np.ones(5, dtype=np.uint8),
                         picky_kernel).result(5.0) == 5
        co.close()

    def test_single_poisoned_item_keeps_direct_error(self):
        co = coalesce.DispatchCoalescer()
        h = co.submit(("solo-p",), np.full(2, POISON, dtype=np.uint8),
                      picky_kernel)
        with pytest.raises(ValueError, match="poisoned"):
            h.result(5.0)
        st = co.stats()
        assert st["batch_faults"] == 1 and st["member_retries"] == 0
        co.close()

    def test_dispatcher_death_fails_queued_never_hangs(self,
                                                       monkeypatch):
        """Scheduler-logic death (not a kernel fault): every queued
        handle errors promptly — no submitter waits out its result()
        timeout on a thread that no longer exists — and later submits
        degrade to inline direct dispatch."""
        co = coalesce.DispatchCoalescer()
        co._ema = 5.0                 # force the queued path
        monkeypatch.setattr(
            co.lane(0), "_pick_key",
            lambda: (_ for _ in ()).throw(RuntimeError("scheduler bug")))
        h = co.submit(("dead",), np.ones(3, dtype=np.uint8),
                      sum_kernel())
        with pytest.raises(RuntimeError, match="dispatcher died"):
            h.result(5.0)
        assert co.stats()["broken"]
        # liveness after death: submits run inline, results still flow
        h2 = co.submit(("dead",), np.ones(4, dtype=np.uint8),
                       sum_kernel())
        assert h2.result(1.0) == 4
        co.close()

    def test_close_fails_pending_handles(self):
        co = coalesce.DispatchCoalescer()
        co._ema = 2.0
        gate = threading.Event()
        h0 = co.submit(("cl",), np.ones(2, dtype=np.uint8),
                       sum_kernel(gate=gate, block_first=True))
        time.sleep(0.05)              # dispatcher blocked in h0
        h1 = co.submit(("cl",), np.ones(3, dtype=np.uint8),
                       sum_kernel())
        co.close()
        with pytest.raises(RuntimeError, match="closed"):
            h1.result(5.0)
        gate.set()                    # the in-flight dispatch finishes
        assert h0.result(5.0) == 2

    def test_engine_falls_back_when_handles_fail(self, tmp_path,
                                                 monkeypatch):
        """A coalescer whose every handle errors must not fail PUTs or
        reads: the engine's coalesced sites fall back to the direct
        kernel and count the fallback."""
        class FailHandle:
            def result(self, timeout=None):
                raise RuntimeError("coalescer dispatcher died: stub")

            def release(self):
                pass

        class BrokenCoalescer:
            def submit(self, key, payload, fn, weight=None, device=0):
                return FailHandle()

        monkeypatch.setenv("MTPU_COALESCE", "1")
        monkeypatch.setattr(coalesce, "get", lambda: BrokenCoalescer())
        es = make_set(tmp_path, n=4, name="fb")
        es.make_bucket("b")
        data = payload(BLOCK_SIZE + 99, seed=90)
        before = DATA_PATH.snapshot()["co_fallbacks"]
        es.put_object("b", "fb", data)
        _, got = es.get_object("b", "fb")
        assert bytes(got) == data
        assert DATA_PATH.snapshot()["co_fallbacks"] > before


def _mixed_workload(es, data_by_obj, ops, seed):
    """One client: run `ops` randomized PUT/GET/ranged-GET ops,
    returning a list of (kind, detail) mismatches (empty == pass)."""
    rng = np.random.default_rng(seed)
    errs = []
    mine = {}
    for i in range(ops):
        kind = ["put", "get", "range"][int(rng.integers(0, 3))]
        if kind == "put" or not data_by_obj:
            size = int(rng.integers(1, 3 * BLOCK_SIZE))
            data = payload(size, seed=seed * 1000 + i)
            name = f"c{seed}-o{i}"
            fi = es.put_object("b", name, data)
            want = hashlib.md5(data).hexdigest()
            if fi.metadata.get("etag") != want:
                errs.append(("etag", name))
            mine[name] = data
        else:
            pool = list(data_by_obj.items()) + list(mine.items())
            name, data = pool[int(rng.integers(0, len(pool)))]
            if kind == "range" and len(data) > 2:
                off = int(rng.integers(0, len(data) - 1))
                ln = int(rng.integers(1, len(data) - off))
                _, got = es.get_object("b", name, offset=off, length=ln)
                if bytes(got) != data[off:off + ln]:
                    errs.append(("range", (name, off, ln)))
            else:
                _, got = es.get_object("b", name)
                if bytes(got) != data:
                    errs.append(("get", name))
    return errs


class TestEngineEquivalence:
    def test_two_client_smoke(self, tmp_path, coalesce_mode):
        """Non-slow tier-1 smoke: 2 clients, small objects, both flag
        values — plus the occupancy metric actually moving when the
        coalescer is on."""
        es = make_set(tmp_path, n=4, name=f"smoke{coalesce_mode}")
        es.make_bucket("b")
        base = {f"pre{i}": payload(BLOCK_SIZE + 17, seed=50 + i)
                for i in range(2)}
        for k, v in base.items():
            es.put_object("b", k, v)
        before = DATA_PATH.snapshot()["co_dispatches"]
        with ThreadPoolExecutor(max_workers=2) as tp:
            futs = [tp.submit(_mixed_workload, es, base, 6, s)
                    for s in (1, 2)]
            errs = [e for f in futs for e in f.result()]
        assert not errs
        if coalesce_mode == "1":
            assert DATA_PATH.snapshot()["co_dispatches"] > before

    @pytest.mark.slow
    def test_concurrent_matrix_stress(self, tmp_path, coalesce_mode):
        """The randomized concurrent matrix from the acceptance
        criteria: 8 clients of mixed PUT/GET/ranged-GET, byte- and
        ETag-exact under both flag values."""
        es = make_set(tmp_path, n=6, parity=2,
                      name=f"stress{coalesce_mode}")
        es.make_bucket("b")
        base = {f"pre{i}": payload(int(sz), seed=60 + i)
                for i, sz in enumerate(
                    [3 * BLOCK_SIZE + 11, BLOCK_SIZE // 2, 777,
                     5 * BLOCK_SIZE])}
        for k, v in base.items():
            es.put_object("b", k, v)
        with ThreadPoolExecutor(max_workers=8) as tp:
            futs = [tp.submit(_mixed_workload, es, base, 10, s)
                    for s in range(1, 9)]
            errs = [e for f in futs for e in f.result()]
        assert not errs

    @pytest.mark.slow
    def test_starvation_guard(self, tmp_path, monkeypatch):
        """A lone small request completes promptly while a heavy PUT
        stream keeps the coalescer saturated — fairness means FIFO
        head-age, not biggest-batch-first."""
        monkeypatch.setenv("MTPU_COALESCE", "1")
        coalesce.reset()
        try:
            es = make_set(tmp_path, n=4, name="starve")
            es.make_bucket("b")
            tiny = payload(64 * 1024, seed=70)
            es.put_object("b", "tiny", tiny)
            stop = threading.Event()

            def hammer(i):
                j = 0
                big = payload(8 * BLOCK_SIZE, seed=80 + i)
                while not stop.is_set():
                    es.put_object("b", f"big{i}-{j}", big)
                    j += 1

            threads = [threading.Thread(target=hammer, args=(i,),
                                        daemon=True) for i in range(4)]
            for t in threads:
                t.start()
            time.sleep(0.3)           # stream is saturating the queue
            try:
                worst = 0.0
                for _ in range(5):
                    t0 = time.monotonic()
                    _, got = es.get_object("b", "tiny")
                    es.put_object("b", "tiny2", tiny)
                    worst = max(worst, time.monotonic() - t0)
                    assert bytes(got) == tiny
            finally:
                stop.set()
                for t in threads:
                    t.join(30.0)
            # Generous CI bound: the window is 250 us and a starved
            # request would sit behind the whole stream (seconds).
            assert worst < 5.0, f"small op starved: {worst:.2f}s"
        finally:
            coalesce.reset()


# -- the shape ladder ----------------------------------------------------------
#
# CPU backend, device kernels: jax host devices stand in for the chip,
# so a batch really crosses to a device and its shape is what the
# program sees.  Geometry EC:2+2 over 256-byte shard rows.

K, M, S, ALGO = 2, 2, 256, "mxh256"
PAD = 32                                   # BATCH_BLOCKS


def _programs():
    from minio_tpu.ops import fused
    return (fused.encode_hash_program(K, M, ALGO),
            fused.hash_rows_program(ALGO),
            fused.verify_transform_program(K, M, (0, 1), (), ALGO),
            fused.verify_transform_program(K, M, (1, 2), (0,), ALGO))


@pytest.fixture
def ladder():
    """A cold coalescer, and no step of the test geometry left built
    for whatever test this worker runs next."""
    coalesce.reset()
    yield
    coalesce.ladder_wait()
    coalesce.reset()
    for prog in _programs():
        prog._built.clear()


def _built_ladder(fn, row_shape):
    coalesce.build_ladder(fn, row_shape)
    coalesce.ladder_wait()
    return fn


def _enc():
    return coalesce.make_encode_kernel(K, M, ALGO, PAD, 0)


def _dig():
    return coalesce.make_digest_kernel(ALGO, PAD * K, 0)


def _blocks(n, seed=1):
    return np.random.default_rng(seed).integers(
        0, 256, (n, K, S), dtype=np.uint8)


def _spy(fn):
    """Record the shape of every array handed to fn's launch."""
    seen, launch = [], fn.launch

    def spy(x, n, spans, ctx):
        seen.append(tuple(x.shape))
        return launch(x, n, spans, ctx)

    fn.launch = spy
    return seen


_FIXED: dict = {}


def _fixed_pad_reference():
    """What the fixed pad gave: a whole multiple of 32 blocks through
    the plain jit, no ladder; row i of it is block i's answer."""
    if not _FIXED:
        from minio_tpu.ops import fused
        x = _blocks(64)
        parity, digests = fused.encode_and_hash(x, K, M, algo=ALGO)
        rows = np.asarray(fused.hash_rows_async(
            x.reshape(64 * K, S), ALGO))
        _FIXED.update(x=x, parity=np.asarray(parity),
                      digests=np.asarray(digests), rows=rows)
    return _FIXED


class TestShapeLadder:
    @pytest.mark.parametrize("n,pad_rows,built,want", [
        (1, 32, "all", 1), (2, 32, "all", 2), (3, 32, "all", 4),
        (9, 32, "all", 16), (17, 32, "all", 32), (32, 32, "all", 32),
        (33, 32, "all", 64), (65, 32, "all", 96),
        (1, 32, (), 32), (1, 32, (4, 16), 4), (5, 32, (4, 16), 16),
        (17, 32, (4, 16), 32), (3, 32, None, 32), (33, 32, None, 64),
        (2, 64, "all", 2), (3, 64, "all", 4), (5, 64, "all", 8),
        (64, 64, "all", 64), (65, 64, "all", 128),
        (7, 1, "all", 7), (7, 48, "all", 48),
    ])
    def test_step_rows(self, n, pad_rows, built, want):
        pred = None if built is None else (
            (lambda rows: True) if built == "all"
            else (lambda rows: rows in built))
        assert coalesce.step_rows(n, pad_rows, pred) == want

    @pytest.mark.parametrize("n", range(1, 34))
    def test_encode_identical_to_fixed_pad(self, ladder, n):
        ref = _fixed_pad_reference()
        fn = _built_ladder(_enc(), (K, S))
        seen = _spy(fn)
        parity, digests = coalesce.get().submit(
            ("enc", "fd", K, M, ALGO, S), ref["x"][:n], fn).result(30)
        assert seen == [(coalesce.step_rows(n, PAD, lambda r: True), K, S)]
        assert np.array_equal(parity, ref["parity"][:n])
        assert np.array_equal(digests, ref["digests"][:, :n])

    @pytest.mark.parametrize("n", range(1, 34))
    def test_get_digest_identical_to_fixed_pad(self, ladder, n):
        ref = _fixed_pad_reference()
        fn = _built_ladder(_dig(), (S,))
        seen = _spy(fn)
        rows = coalesce.get().submit(
            ("digest", ALGO, S, PAD * K),
            ref["x"][:n].reshape(n * K, S), fn).result(30)
        assert seen == [(coalesce.step_rows(n * K, PAD * K,
                                            lambda r: True), S)]
        assert np.array_equal(rows, ref["rows"][:n * K])

    @pytest.mark.parametrize("path", ["inline", "serial", "pipelined"])
    @pytest.mark.parametrize("n,want", [(1, 1), (3, 4), (17, 32),
                                        (33, 64)])
    def test_launch_sees_the_step(self, ladder, monkeypatch, path, n,
                                  want):
        monkeypatch.setenv("MTPU_H2D_PIPELINE",
                           "1" if path == "pipelined" else "0")
        fn = _built_ladder(_enc(), (K, S))
        seen = _spy(fn)
        co = coalesce.get()
        if path != "inline":
            co._ema = 2.0                 # traffic: queue, do not inline
        before = DATA_PATH.snapshot()["lanes"].get(0, {})
        x = _blocks(n, seed=n)
        parity, _ = co.submit(("enc", "fd", K, M, ALGO, S), x,
                              fn).result(30)
        assert seen == [(want, K, S)] and parity.shape == (n, M, S)
        st = co.lane_stats()[0]
        assert st["pipeline_dispatches"] == (path == "pipelined")
        assert (co._thread is None) == (path == "inline")
        row = DATA_PATH.snapshot()["lanes"][0]
        assert row["rows"] - before.get("rows", 0) == n
        assert row["padded_rows"] - before.get("padded_rows", 0) == want

    def test_only_a_built_step_serves_and_the_lane_never_compiles(
            self, ladder):
        """Top step built: a 1-row batch runs at 32.  Step 1 built: it
        runs at 1.  Neither dispatch traces or compiles anything: the
        jit behind the program is never entered."""
        prog = _programs()[0]
        jit_entries = prog.jit._cache_size()
        fn = _enc()
        prog.build((PAD, K, S), 0)
        seen = _spy(fn)
        co = coalesce.get()
        co._ema = 2.0                     # on the lane thread
        compiles = DATA_PATH.snapshot()["jit_compiles"]
        x = _blocks(1)
        p32, d32 = co.submit(("enc", "fd", K, M, ALGO, S), x,
                             fn).result(30)
        assert seen == [(PAD, K, S)]
        prog.build((1, K, S), 0)          # off the lane thread
        compiles_built = DATA_PATH.snapshot()["jit_compiles"]
        p1, d1 = co.submit(("enc", "fd", K, M, ALGO, S), x,
                           fn).result(30)
        assert seen == [(PAD, K, S), (1, K, S)]
        assert np.array_equal(p1, p32) and np.array_equal(d1, d32)
        assert prog.jit._cache_size() == jit_entries
        snap = DATA_PATH.snapshot()["jit_compiles"]
        assert compiles_built - compiles <= 1 and snap == compiles_built

    @pytest.mark.parametrize("padded_blocks", [False, True])
    def test_ladder_is_built_top_step_first(self, ladder, monkeypatch,
                                            padded_blocks):
        """Encode, GET digest and the geometry's one decode program;
        the verify-only hash only where every GET takes the generic
        read (`padded_blocks`: K does not divide the block)."""
        from minio_tpu.ops import fused
        order = []
        build = fused.Program.build

        def spy(self, shape, device):
            order.append((self.jit.__name__, shape[0]))
            return build(self, shape, device)

        monkeypatch.setattr(fused.Program, "build", spy)
        coalesce.build_geometry_ladder(K, M, S, ALGO, PAD, 0,
                                       padded_blocks=padded_blocks)
        coalesce.ladder_wait()
        steps = (32, 16, 8, 4, 2, 1)
        assert order == (
            [(f"encode_hash_k{K}m{M}_{ALGO}", r) for r in steps]
            + [(f"hash_rows_{ALGO}", r * K) for r in steps]
            + [(f"verify_transform_k{K}m{M}_{ALGO}", r) for r in steps]
            + [(f"verify_{ALGO}", r) for r in steps] * padded_blocks)
        enc, dig, verify, decode = _programs()
        assert all(enc.built((r, K, S), 0) and dig.built((r * K, S), 0)
                   and decode.built((r, K, S), 0)
                   for r in coalesce.LADDER)
        assert bool(verify._built) == padded_blocks

    def test_a_step_that_fails_to_build_leaves_the_next_one_up(
            self, ladder, monkeypatch, capsys):
        from minio_tpu.ops import fused
        build = fused.Program.build

        def flaky(self, shape, device):
            if shape[0] == 1:
                raise RuntimeError("no such tile")
            return build(self, shape, device)

        monkeypatch.setattr(fused.Program, "build", flaky)
        fn = _built_ladder(_enc(), (K, S))
        assert "not built" in capsys.readouterr().err
        assert coalesce.kernel_rows(fn, 1, (K, S)) == 2

    @pytest.mark.parametrize("targets,want", [((), 1), ((0,), 1)])
    def test_verify_kernel_takes_the_ladder_with_targets_too(
            self, ladder, tmp_path, targets, want):
        """PR 35: the rows to rebuild reach the geometry's one decode
        program as its matrix operand, so it has a ladder like the
        hash-only program (before: a program a pattern, one shape)."""
        es = make_set(tmp_path, name="vt")
        sources = (1, 2) if targets else (0, 1)
        fn = es.math.vt_kernel(K, M, sources, targets, ALGO, device=0)
        assert fn.ladder and fn.program is not None
        _built_ladder(fn, (K, S))
        seen = _spy(fn)
        x = _blocks(1)
        digests, out = coalesce.get().submit(
            ("vt", K, M, sources, targets, ALGO, S), x, fn).result(60)
        assert seen == [(want, K, S)]
        assert digests.shape == (1, K, 32)
        assert (out is None) == (not targets)

    @pytest.mark.parametrize("targets", [(), (0,)])
    def test_an_unbuilt_program_is_built_by_its_submitter(
            self, ladder, tmp_path, monkeypatch, targets):
        """Nothing built, not even the top step (a geometry nobody
        announced, a verify+transform variant met for the first time):
        the submitting thread builds the 32-row shape before it queues
        the item, so the lane thread runs a built executable and never
        enters the jit."""
        from minio_tpu.ops import fused
        sources = (1, 2) if targets else (0, 1)
        fn = make_set(tmp_path, name="cold").math.vt_kernel(
            K, M, sources, targets, ALGO, device=0)
        prog = fn.program()
        prog._built.clear()
        builds, build = [], fused.Program.build

        def spy(self, shape, device):
            if not self.built(shape, device):
                builds.append((threading.current_thread().name, shape))
            return build(self, shape, device)

        monkeypatch.setattr(fused.Program, "build", spy)
        jit_entries = prog.jit._cache_size()
        seen = _spy(fn)
        co = coalesce.get()
        co._ema = 2.0                     # through the lane thread
        for _ in range(2):
            digests, _ = co.submit(
                ("vt", K, M, sources, targets, ALGO, S), _blocks(1),
                fn).result(60)
        assert digests.shape == (1, K, 32)
        assert builds == [(threading.current_thread().name, (PAD, K, S))]
        assert seen == [(PAD, K, S)] * 2 and co._thread is not None
        assert prog.jit._cache_size() == jit_entries
        prog._built.clear()

    def test_building_a_kernel_reaches_for_no_program(self, monkeypatch):
        """A pool worker builds these kernels to submit their keys to
        the device owner: building one must not touch JAX (the worker
        has no backend), so the program is looked up at dispatch."""
        from minio_tpu.ops import fused

        def no_jax(*a, **kw):
            raise AssertionError("a kernel builder asked for a program")

        for name in ("encode_hash_program", "hash_rows_program",
                     "verify_transform_program"):
            monkeypatch.setattr(fused, name, no_jax)
        for fn in (_enc(), _dig(),
                   coalesce.make_verify_kernel(K, M, (0, 1), (), ALGO,
                                               PAD, 0),
                   coalesce.make_verify_kernel(K, M, (1, 2), (0,), ALGO,
                                               PAD, 0)):
            assert fn.pad_rows and callable(fn.program)

    @pytest.mark.parametrize("key", [
        ("enc", "fd", K, M, ALGO, S), ("digest", ALGO, S, PAD * K),
        ("vt", K, M, (0, 1), (), ALGO, S)])
    def test_ipc_kernels_pad_by_the_same_rule(self, ladder, monkeypatch,
                                              key):
        """The owner-side kernels are the engine's builders: same
        program, and their shapes come out of coalesce.step_rows."""
        from minio_tpu.ops import ipc_dispatch as ipc
        fn = ipc.kernel_from_key(key, device=0)
        prog = dict(zip(("enc", "digest", "vt"), _programs()))[key[0]]
        assert fn.program() is prog and fn.pad_rows in (PAD, PAD * K)
        asked = []

        def rule(n, pad_rows, built=None):
            asked.append((n, pad_rows))
            return 8

        monkeypatch.setattr(coalesce, "step_rows", rule)
        seen = _spy(fn)
        x = _blocks(3)
        if key[0] == "digest":
            x = x.reshape(3 * K, S)
        fn(x, [(0, x.shape[0])], None)              # the kernel's own pad
        coalesce.get().submit(key, x, fn).result(30)    # the lane's
        assert asked == [(x.shape[0], fn.pad_rows)] * 2
        assert seen == [(8,) + x.shape[1:]] * 2
