"""Serving-plane resilience (ISSUE 12): load-shedding admission
control, request deadlines, the dispatch stall breaker (/healthz flip,
fast-fail, recovery), verified hot-swap with canary rollback, graceful
drain, and the typed HTTP error surface.

Reference behavior baseline: the reference deployment
(examples/web_demo/app.py) has none of this — an overloaded or hung
Classifier takes every client down with it. Here every failure mode is
typed, bounded, and journaled (docs/serving.md "Resilience").
"""

import json
import os
import threading
import time
import urllib.request
import urllib.error

import numpy as np
import pytest

import caffe_mpi_tpu.pycaffe as caffe
from caffe_mpi_tpu.serving import (DeadlineError, EngineClosedError,
                                   EngineUnhealthyError, ServingEngine,
                                   ShedError, SnapshotWatcher, SwapError)
from caffe_mpi_tpu.serving.http_front import make_server
from caffe_mpi_tpu.utils import resilience

TOY_NET = """
name: "toy"
layer {{ name: "data" type: "Input" top: "data"
        input_param {{ shape {{ dim: {batch} dim: 3 dim: 8 dim: 8 }} }} }}
layer {{ name: "ip" type: "InnerProduct" bottom: "data" top: "score"
        inner_product_param {{ num_output: 5
          weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "prob" type: "Softmax" bottom: "score" top: "prob" }}
"""


def write_toy(tmp_path, batch=4, name="deploy.prototxt"):
    model = tmp_path / name
    model.write_text(TOY_NET.format(batch=batch))
    net = caffe.Net(str(model), caffe.TEST)
    weights = str(tmp_path / (name + ".caffemodel"))
    net.save(weights)
    return str(model), weights


def imgs(n, seed=0):
    r = np.random.RandomState(seed)
    return [r.rand(8, 8, 3).astype(np.float32) for _ in range(n)]


def wait_dispatcher_took(eng, timeout=5.0):
    """Block until the dispatcher has pulled every pending request into
    a batch. The stall-vs-deadline tests need request A *inside* its
    injected dispatch stall before request B is submitted; on a loaded
    host the dispatcher thread can lag both submits and A+B would ride
    one batch."""
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        with eng._batcher._cv:
            if not eng._batcher._pending:
                return
        time.sleep(0.005)
    raise TimeoutError("dispatcher never took the pending request")


def publish_snapshot(prefix, it, model_file, scale=3.0, weights_from=None):
    """Write a verified flat snapshot set (<prefix>_iter_<it>.caffemodel
    + .solverstate + crc32c manifest) whose ip weights are `scale`x the
    `weights_from` file's — the swap feed the watcher consumes."""
    net = caffe.Net(model_file, caffe.TEST)
    if weights_from:
        net.copy_from(weights_from)
    net.params["ip"][0].data = net.params["ip"][0].data * scale
    mpath = f"{prefix}_iter_{it}.caffemodel"
    net.save(mpath)
    spath = f"{prefix}_iter_{it}.solverstate"
    with open(spath, "wb") as f:  # the watcher never loads solver state
        f.write(b"state-stub")
    resilience.write_snapshot_manifest(spath, it,
                                       {"model": mpath, "state": spath})
    return mpath


@pytest.fixture
def faults():
    """Configure the fault plane for one test and always restore it."""
    def configure(spec):
        resilience.FAULTS.configure(spec)
    yield configure
    resilience.FAULTS.configure(os.environ.get("CAFFE_TPU_FAULTS", ""))


# ---------------------------------------------------------------------------
# load-shedding admission control (serve_queue_limit)

class TestAdmissionControl:
    def test_over_limit_submit_sheds_typed_and_depth_is_bounded(
            self, tmp_path):
        model, weights = write_toy(tmp_path)
        # a 60s window keeps the backlog parked in the queue
        with ServingEngine(window_ms=60_000, queue_limit=2) as eng:
            eng.load_model("m", model, weights)
            f1 = eng.submit("m", imgs(1)[0])
            f2 = eng.submit("m", imgs(1)[0])
            with pytest.raises(ShedError) as ei:
                eng.submit("m", imgs(1)[0])
            assert ei.value.http_status == 429 and ei.value.kind == "shed"
            st = eng.stats()
            assert st["shed_requests"] == 1
            assert st["max_queue_depth"] == 2  # held AT the limit
            assert not f1.done() and not f2.done()

    def test_deterministic_shed_count_under_overload(self, tmp_path):
        # offered load > capacity with the dispatcher parked: exactly
        # offered - limit submits shed, queue depth never passes limit.
        # The limit stays BELOW the max bucket (4), so a full group can
        # never close the 60s window early and drain mid-loop — the
        # exact counts are deterministic, not a race with the
        # dispatcher.
        model, weights = write_toy(tmp_path)
        with ServingEngine(window_ms=60_000, queue_limit=3) as eng:
            eng.load_model("m", model, weights)
            accepted = shed = 0
            for im in imgs(20):
                try:
                    eng.submit("m", im)
                    accepted += 1
                except ShedError:
                    shed += 1
            assert (accepted, shed) == (3, 17)
            assert eng.stats()["max_queue_depth"] == 3

    def test_zero_limit_is_unbounded(self, tmp_path):
        model, weights = write_toy(tmp_path)
        with ServingEngine(window_ms=60_000) as eng:  # default 0
            eng.load_model("m", model, weights)
            for im in imgs(12):
                eng.submit("m", im)
            assert eng.stats()["shed_requests"] == 0

    def test_negative_resilience_knobs_rejected_at_init(self):
        with pytest.raises(ValueError, match="serve_queue_limit"):
            ServingEngine(queue_limit=-1, start=False)
        with pytest.raises(ValueError, match="serve_deadline_ms"):
            ServingEngine(deadline_ms=-1, start=False)
        with pytest.raises(ValueError, match="serve_stall_s"):
            ServingEngine(stall_s=-0.5, start=False)


# ---------------------------------------------------------------------------
# request deadlines (serve_deadline_ms)

class TestDeadline:
    def test_request_aged_past_deadline_fails_typed(self, tmp_path,
                                                    faults):
        # the dispatcher is busy 0.6s inside request A's dispatch (an
        # injected stall, breaker OFF); request B, submitted right
        # behind it with a 100ms deadline, must fail typed at its
        # window close instead of riding a batch whose result it
        # would discard
        model, weights = write_toy(tmp_path)
        faults("serve_dispatch_stall:1:0:0.6")
        with ServingEngine(window_ms=0, deadline_ms=100) as eng:
            eng.load_model("m", model, weights)
            fa = eng.submit("m", imgs(1)[0])
            wait_dispatcher_took(eng)  # A is inside the stall, alone
            fb = eng.submit("m", imgs(1)[0])
            assert fa.result(timeout=10).shape == (5,)
            with pytest.raises(DeadlineError) as ei:
                fb.result(timeout=10)
            assert ei.value.http_status == 504
            assert ei.value.kind == "deadline"
            st = eng.stats()
            assert st["deadline_failures"] == 1

    def test_window_clamped_to_deadline(self, tmp_path):
        # a 60s window with a 150ms deadline must still dispatch the
        # request (the batch closes AT the deadline, not the window)
        model, weights = write_toy(tmp_path)
        with ServingEngine(window_ms=60_000, deadline_ms=150) as eng:
            eng.load_model("m", model, weights)
            t0 = time.perf_counter()
            out = eng.submit("m", imgs(1)[0]).result(timeout=10)
            assert out.shape == (5,)
            assert time.perf_counter() - t0 < 5.0
            assert eng.stats()["deadline_failures"] == 0

    def test_deadline_off_is_free(self, tmp_path):
        model, weights = write_toy(tmp_path)
        with ServingEngine(window_ms=0) as eng:
            eng.load_model("m", model, weights)
            assert eng.classify("m", imgs(3)).shape == (3, 5)
            assert eng.stats()["deadline_failures"] == 0


# ---------------------------------------------------------------------------
# dispatch stall breaker

class TestStallBreaker:
    def test_stall_trips_breaker_fast_fails_then_recovers(
            self, tmp_path, faults):
        model, weights = write_toy(tmp_path)
        faults("serve_dispatch_stall:1:0:1.5")
        with ServingEngine(window_ms=0, stall_s=0.3,
                           journal=str(tmp_path / "m")) as eng:
            eng.load_model("m", model, weights)
            fut = eng.submit("m", imgs(1)[0])
            # the in-flight future fails from the MONITOR thread while
            # the dispatch thread is still wedged in the 1.5s stall
            with pytest.raises(DeadlineError):
                fut.result(timeout=10)
            assert not eng.healthy
            h = eng.health()
            assert h["healthy"] is False
            assert h["breaker"]["state"] == "open"
            assert h["breaker"]["section"].startswith("dispatch:")
            # new requests fast-fail well inside the stall window
            t0 = time.perf_counter()
            with pytest.raises(EngineUnhealthyError) as ei:
                eng.submit("m", imgs(1)[0])
            assert time.perf_counter() - t0 < 0.3
            assert ei.value.http_status == 503
            assert ei.value.kind == "unhealthy"
            # journaled for the operator
            doc = json.load(open(str(tmp_path / "m") + ".serve.run.json"))
            assert doc["reason"].startswith("serve_stall:dispatch")
            # probe while the stalled call is still wedged: refused
            assert eng.probe_recovery(timeout=1) is False
            # the injected stall ends -> the wedge retires normally
            eng.drain(timeout=10)
            assert eng.probe_recovery(timeout=10) is True
            assert eng.healthy
            # serving resumes, zero new compiles through the whole trip
            assert eng.classify("m", imgs(2)).shape == (2, 5)
            st = eng.stats()
            assert st["stall_trips"] == 1
            assert st["healthy"] is True
            assert st["compile_count"] == st["warmed_buckets"]

    def test_trip_drains_parked_backlog_too(self, tmp_path, faults):
        # a request PARKED in the queue when the breaker trips has a
        # wedged dispatcher — it must fail typed with the in-flight
        # one, not stay PENDING forever
        model, weights = write_toy(tmp_path)
        faults("serve_dispatch_stall:1:0:1.2")
        with ServingEngine(window_ms=0, stall_s=0.3) as eng:
            eng.load_model("m", model, weights)
            fa = eng.submit("m", imgs(1)[0])  # wedges the dispatcher
            fb = eng.submit("m", imgs(1)[0])  # parks behind it
            with pytest.raises(DeadlineError):
                fa.result(timeout=10)
            with pytest.raises(DeadlineError):
                fb.result(timeout=10)
            eng.drain(timeout=10)

    def test_close_stops_breaker_monitor_thread(self, tmp_path):
        # an embedding app cycling engines must not leak one watchdog
        # poller per engine
        model, weights = write_toy(tmp_path)
        eng = ServingEngine(window_ms=0, stall_s=5.0)
        eng.load_model("m", model, weights)
        wd = eng._watchdog
        assert wd is not None and wd._thread.is_alive()
        eng.close()
        wd._thread.join(timeout=5)
        assert not wd._thread.is_alive()
        assert eng._watchdog is None

    def test_probe_recovery_after_close_does_not_rearm(self, tmp_path):
        # a recovery-probe thread that loses the race with close() must
        # not re-arm a fresh watchdog (a monitor thread nobody would
        # ever stop) or flip a closed engine back to healthy
        model, weights = write_toy(tmp_path)
        eng = ServingEngine(window_ms=0, stall_s=5.0)
        eng.load_model("m", model, weights)
        eng._on_stall("dispatch:m", 9.9)   # breaker open
        assert not eng.healthy
        eng.close()
        assert eng.probe_recovery(timeout=5) is False
        assert eng._watchdog is None
        assert not eng.healthy

    def test_breaker_off_by_default(self, tmp_path):
        model, weights = write_toy(tmp_path)
        with ServingEngine(window_ms=0) as eng:
            eng.load_model("m", model, weights)
            assert eng._watchdog is None  # zero threads when off
            assert eng.health()["healthy"] is True


# ---------------------------------------------------------------------------
# verified hot-swap + canary rollback

class TestHotSwap:
    def _engine(self, tmp_path, **kw):
        model, weights = write_toy(tmp_path)
        eng = ServingEngine(window_ms=0, journal=str(tmp_path / "m"), **kw)
        eng.load_model("m", model, weights)
        return eng, model, weights

    def test_watch_swaps_newly_verified_snapshot_zero_recompiles(
            self, tmp_path):
        eng, model, weights = self._engine(tmp_path)
        with eng:
            prefix = str(tmp_path / "train" / "snap")
            os.makedirs(os.path.dirname(prefix))
            watcher = SnapshotWatcher(eng, "m", prefix, poll_s=0.1)
            base = eng.classify("m", imgs(3, seed=7))
            assert watcher.check_once() is False  # nothing published yet
            w2 = publish_snapshot(prefix, 10, model, scale=3.0,
                                  weights_from=weights)
            compiles = eng.compile_count
            assert watcher.check_once() is True
            assert eng.swaps == 1
            # the swap compiled NOTHING: same bucket programs, new bytes
            assert eng.compile_count == compiles
            assert eng.compile_count == eng.warmed_buckets
            got = eng.classify("m", imgs(3, seed=7))
            assert not np.allclose(got, base)
            # scores now match a cold classifier on the new weights
            clf = caffe.Classifier(model, w2, image_dims=(8, 8))
            want = clf.predict(imgs(3, seed=7), oversample=False)
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
            doc = json.load(open(str(tmp_path / "m") + ".serve.run.json"))
            assert doc["reason"] == "swap"
            assert doc["source"] == "iter_10"

    def test_corrupt_swap_rejected_previous_weights_bitwise(
            self, tmp_path, faults):
        eng, model, weights = self._engine(tmp_path)
        with eng:
            prefix = str(tmp_path / "snap")
            base = eng.classify("m", imgs(2, seed=1))
            publish_snapshot(prefix, 5, model, weights_from=weights)
            # post-manifest bitrot: verify must reject before any byte
            # reaches the engine
            faults("swap_corrupt:1")
            watcher = SnapshotWatcher(eng, "m", prefix, poll_s=0.1)
            assert watcher.check_once() is False
            assert eng.swaps == 0 and eng.swap_rejections == 1
            after = eng.classify("m", imgs(2, seed=1))
            np.testing.assert_array_equal(base, after)  # BITWISE same
            doc = json.load(open(str(tmp_path / "m") + ".serve.run.json"))
            assert doc["reason"] == "swap_rejected"
            assert "crc" in doc["swap_reason"]
            # rot does not heal: the iteration is blacklisted, a later
            # GOOD snapshot still swaps
            publish_snapshot(prefix, 6, model, scale=2.0,
                             weights_from=weights)
            assert watcher.check_once() is True
            assert eng.swaps == 1

    def test_canary_rollback_on_nonfinite_scores(self, tmp_path, faults):
        eng, model, weights = self._engine(tmp_path)
        with eng:
            prefix = str(tmp_path / "snap")
            base = eng.classify("m", imgs(2, seed=2))
            publish_snapshot(prefix, 7, model, weights_from=weights)
            faults("swap_canary_bad:1")
            watcher = SnapshotWatcher(eng, "m", prefix, poll_s=0.1)
            assert watcher.check_once() is False
            assert eng.swap_rejections == 1 and eng.swaps == 0
            after = eng.classify("m", imgs(2, seed=2))
            np.testing.assert_array_equal(base, after)
            doc = json.load(open(str(tmp_path / "m") + ".serve.run.json"))
            assert doc["reason"] == "swap_rejected"
            assert "non-finite" in doc["swap_reason"]

    def test_shape_mismatched_weights_rejected_by_canary(self, tmp_path):
        # a snapshot from a DIFFERENT architecture (10-way head) loads
        # as a file but cannot fit the compiled programs' params tree
        eng, model, weights = self._engine(tmp_path)
        with eng:
            other = tmp_path / "other.prototxt"
            other.write_text(TOY_NET.format(batch=4).replace(
                "num_output: 5", "num_output: 10"))
            onet = caffe.Net(str(other), caffe.TEST)
            ow = str(tmp_path / "other.caffemodel")
            onet.save(ow)
            base = eng.classify("m", imgs(2, seed=3))
            with pytest.raises(SwapError):
                eng.swap_weights("m", ow)
            assert eng.swap_rejections == 1
            after = eng.classify("m", imgs(2, seed=3))
            np.testing.assert_array_equal(base, after)

    def test_swap_under_live_traffic_all_futures_resolve(self, tmp_path):
        eng, model, weights = self._engine(tmp_path)
        with eng:
            prefix = str(tmp_path / "snap")
            w2 = publish_snapshot(prefix, 3, model, scale=3.0,
                                  weights_from=weights)
            futures = []
            stop = threading.Event()

            def traffic():
                while not stop.is_set():
                    futures.append(eng.submit("m", imgs(1)[0]))
                    time.sleep(0.002)

            t = threading.Thread(target=traffic, daemon=True)
            t.start()
            time.sleep(0.05)
            eng.swap_weights("m", w2)
            time.sleep(0.05)
            stop.set()
            t.join(timeout=5)
            eng.drain(timeout=30)
            rows = [f.result(timeout=5) for f in futures]
            assert all(r.shape == (5,) for r in rows)
            assert eng.compile_count == eng.warmed_buckets
            assert eng.swaps == 1

    def test_orbax_sets_are_skipped_not_rejected(self, tmp_path):
        eng, model, weights = self._engine(tmp_path)
        with eng:
            prefix = str(tmp_path / "snap")
            d = f"{prefix}_iter_4.orbax"
            os.makedirs(d)
            with open(os.path.join(d, "shard0"), "wb") as f:
                f.write(b"shard-bytes")
            resilience.write_sharded_manifest(d, 4)
            watcher = SnapshotWatcher(eng, "m", prefix, poll_s=0.1)
            assert watcher.check_once() is False
            assert eng.swap_rejections == 0  # skip, not a rejection


# ---------------------------------------------------------------------------
# graceful drain

class TestGracefulDrain:
    def test_shutdown_resolves_every_inflight_future(self, tmp_path):
        model, weights = write_toy(tmp_path)
        eng = ServingEngine(window_ms=60_000)  # window parks the batch
        eng.load_model("m", model, weights)
        futs = [eng.submit("m", im) for im in imgs(3)]
        eng.shutdown(timeout=30)  # stop accepting -> flush -> resolve
        rows = [f.result(timeout=1) for f in futs]  # NOT cancelled
        assert all(r.shape == (5,) for r in rows)
        with pytest.raises(EngineClosedError) as ei:
            eng.submit("m", imgs(1)[0])
        assert ei.value.http_status == 503 and ei.value.kind == "closed"

    def test_shutdown_idempotent_and_empty(self, tmp_path):
        model, weights = write_toy(tmp_path)
        eng = ServingEngine(window_ms=0)
        eng.load_model("m", model, weights)
        eng.shutdown()
        eng.shutdown()  # second call is a no-op, not a hang


# ---------------------------------------------------------------------------
# typed HTTP surface (/healthz, /readyz, 429/503/504, 400 stays 400)

class _Server:
    def __init__(self, eng):
        self.srv = make_server(eng, "m", port=0)
        self.port = self.srv.server_address[1]
        self.thread = threading.Thread(target=self.srv.serve_forever,
                                       daemon=True)
        self.thread.start()

    def get(self, path):
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{self.port}{path}", timeout=30) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def post_png(self, data=None):
        import io as _io
        from PIL import Image
        if data is None:
            buf = _io.BytesIO()
            Image.fromarray(np.zeros((8, 8, 3), np.uint8)).save(
                buf, format="PNG")
            data = buf.getvalue()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}/classify", data=data,
            headers={"Content-Type": "image/png"})
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def close(self):
        self.srv.shutdown()


class TestHttpFront:
    def test_healthz_readyz_and_stats_roundtrip(self, tmp_path):
        model, weights = write_toy(tmp_path)
        with ServingEngine(window_ms=0) as eng:
            eng.load_model("m", model, weights)
            web = _Server(eng)
            try:
                code, doc = web.get("/healthz")
                assert code == 200 and doc["healthy"] is True
                assert "last_dispatch_age_s" in doc
                code, doc = web.get("/readyz")
                assert code == 200 and doc["ready"] is True
                assert doc["compile_count"] == doc["warmed_buckets"]
                code, doc = web.get("/stats")
                assert code == 200 and doc["healthy"] is True
            finally:
                web.close()

    def test_readyz_503_with_empty_zoo(self):
        with ServingEngine(window_ms=0) as eng:
            web = _Server(eng)
            try:
                code, doc = web.get("/readyz")
                assert code == 503 and doc["ready"] is False
            finally:
                web.close()

    def test_shed_is_429_with_machine_readable_body(self, tmp_path):
        model, weights = write_toy(tmp_path)
        with ServingEngine(window_ms=60_000, queue_limit=1) as eng:
            eng.load_model("m", model, weights)
            eng.submit("m", imgs(1)[0])  # fills the backlog
            web = _Server(eng)
            try:
                code, doc = web.post_png()
                assert code == 429
                assert doc["kind"] == "shed"
                assert "serve_queue_limit" in doc["error"]
            finally:
                web.close()

    def test_breaker_open_is_503_and_healthz_flips(self, tmp_path,
                                                   faults):
        model, weights = write_toy(tmp_path)
        faults("serve_dispatch_stall:1:0:1.0")
        with ServingEngine(window_ms=0, stall_s=0.25) as eng:
            eng.load_model("m", model, weights)
            web = _Server(eng)
            try:
                fut = eng.submit("m", imgs(1)[0])  # trips the breaker
                with pytest.raises(DeadlineError):
                    fut.result(timeout=10)
                code, doc = web.get("/healthz")
                assert code == 503 and doc["healthy"] is False
                code, doc = web.post_png()
                assert code == 503 and doc["kind"] == "unhealthy"
                eng.drain(timeout=10)
            finally:
                web.close()

    def test_deadline_is_504_over_http(self, tmp_path, faults):
        model, weights = write_toy(tmp_path)
        faults("serve_dispatch_stall:1:0:0.6")
        with ServingEngine(window_ms=0, deadline_ms=100) as eng:
            eng.load_model("m", model, weights)
            web = _Server(eng)
            try:
                fa = eng.submit("m", imgs(1)[0])  # occupies dispatcher
                wait_dispatcher_took(eng)
                code, doc = web.post_png()
                assert code == 504 and doc["kind"] == "deadline"
                fa.result(timeout=10)
            finally:
                web.close()

    def test_closed_engine_is_503(self, tmp_path):
        model, weights = write_toy(tmp_path)
        eng = ServingEngine(window_ms=0)
        eng.load_model("m", model, weights)
        web = _Server(eng)
        try:
            eng.close()
            code, doc = web.post_png()
            assert code == 503 and doc["kind"] == "closed"
        finally:
            web.close()

    def test_bad_upload_stays_400(self, tmp_path):
        model, weights = write_toy(tmp_path)
        with ServingEngine(window_ms=0) as eng:
            eng.load_model("m", model, weights)
            web = _Server(eng)
            try:
                code, doc = web.post_png(data=b"this is not an image")
                assert code == 400
                assert doc["kind"] == "bad_request"
                assert "decode" in doc["error"]
            finally:
                web.close()


# ---------------------------------------------------------------------------
# failure-path behavior fixes (ISSUE 20): the sites the new lint passes
# flagged and we FIXED rather than waived — each fix gets a regression


class TestFailurePathLiveness:
    def test_dispatcher_crash_is_contained_typed_and_journaled(
            self, tmp_path):
        """thread-crash fix: an exception out of the dispatch loop must
        fail in-flight futures TYPED, journal serve_dispatcher_crash,
        and re-enter the loop — never die silently with the backlog
        parked behind a dead thread (the PR 11 wedge, as a crash)."""
        model, weights = write_toy(tmp_path)
        journal = str(tmp_path / "serve")
        with ServingEngine(window_ms=0, journal=journal) as eng:
            eng.load_model("m", model, weights)
            # prove the path works before the injected crash
            assert eng.classify("m", imgs(1)).shape == (1, 5)
            real = eng._batcher._take_group
            state = {"armed": True}

            def boom(*a, **kw):
                if state["armed"]:
                    state["armed"] = False
                    raise RuntimeError("injected dispatcher crash")
                return real(*a, **kw)

            eng._batcher._take_group = boom
            fut = eng.submit("m", imgs(1)[0])
            with pytest.raises(EngineUnhealthyError) as ei:
                fut.result(timeout=10)
            assert "dispatcher crashed" in str(ei.value)
            # fail_inflight resolves the future BEFORE the journal
            # write lands — poll briefly for the manifest
            jpath = journal + ".serve.run.json"
            deadline = time.perf_counter() + 5.0
            while not os.path.exists(jpath) \
                    and time.perf_counter() < deadline:
                time.sleep(0.01)
            doc = json.load(open(jpath))
            assert doc["reason"] == "serve_dispatcher_crash"
            assert "injected dispatcher crash" in doc["error"]
            # the loop re-entered: the SAME thread serves the next one
            assert eng.classify("m", imgs(1, seed=1)).shape == (1, 5)

    def test_shed_submit_constructs_no_future(self, tmp_path, monkeypatch):
        """future-resolution fix (the PR 7 shape): an admission raise —
        shed, closed, unhealthy — must happen BEFORE the request and
        its Future exist, so a rejected submit can never strand a
        pending-forever future."""
        from caffe_mpi_tpu.serving import batcher as batcher_mod
        model, weights = write_toy(tmp_path)
        built = []
        real_req = batcher_mod._Request

        def counting_req(*a, **kw):
            r = real_req(*a, **kw)
            built.append(r)
            return r

        monkeypatch.setattr(batcher_mod, "_Request", counting_req)
        with ServingEngine(window_ms=60_000, queue_limit=1) as eng:
            eng.load_model("m", model, weights)
            eng.submit("m", imgs(1)[0])
            assert len(built) == 1
            with pytest.raises(ShedError):
                eng.submit("m", imgs(1)[0])
            assert len(built) == 1  # the shed built nothing
            eng._healthy = False
            with pytest.raises(EngineUnhealthyError):
                eng.submit("m", imgs(1)[0])
            eng._healthy = True
            assert len(built) == 1
        with pytest.raises(EngineClosedError):
            eng.submit("m", imgs(1)[0])
        assert len(built) == 1

    def test_probe_thread_crash_journals_not_silent(self, tmp_path):
        """thread-crash fix: the async recovery-probe thread entry must
        catch a raising probe_recovery and journal serve_probe_crash —
        a silent death leaves the breaker open with no signal."""
        model, weights = write_toy(tmp_path)
        journal = str(tmp_path / "probe")
        with ServingEngine(window_ms=0, journal=journal) as eng:
            eng.load_model("m", model, weights)
            eng.probe_recovery = lambda *a, **kw: (_ for _ in ()).throw(
                RuntimeError("injected probe crash"))
            eng._probe_recovery_guarded()  # must not raise
            doc = json.load(open(journal + ".serve.run.json"))
            assert doc["reason"] == "serve_probe_crash"
            assert "injected probe crash" in doc["error"]

    def test_classify_gather_is_deadline_bounded(self, tmp_path):
        """deadline-discipline fix: classify's future gather takes a
        timeout — a wedged dispatcher surfaces as TimeoutError in the
        caller, never an unbounded f.result() hang."""
        import concurrent.futures as cf
        model, weights = write_toy(tmp_path)
        with ServingEngine(window_ms=0) as eng:
            eng.load_model("m", model, weights)
            eng.submit = lambda *a, **kw: cf.Future()  # never resolves
            t0 = time.perf_counter()
            with pytest.raises(cf.TimeoutError):
                eng.classify("m", imgs(1), timeout=0.2)
            assert time.perf_counter() - t0 < 5.0

    def test_wait_snapshots_join_is_bounded(self):
        """deadline-discipline fix: a wedged async snapshot writer
        (a device fetch that never returns) must fail wait_snapshots loudly
        within the timeout, not hang the exit path forever."""
        from caffe_mpi_tpu.solver.solver import Solver

        class Stub:
            pass

        stub = Stub()
        release = threading.Event()
        stub._snapshot_thread = threading.Thread(
            target=release.wait, args=(10.0,), daemon=True)
        stub._snapshot_thread.start()
        try:
            with pytest.raises(RuntimeError) as ei:
                Solver.wait_snapshots(stub, timeout=0.1)
            assert "wedged" in str(ei.value)
        finally:
            release.set()
            stub._snapshot_thread.join(5.0)

    def test_wait_snapshots_reraises_writer_error_after_join(self):
        """The bounded join must still deliver a finished writer's
        failure: a checkpoint the user believes exists but doesn't
        must not pass silently."""
        from caffe_mpi_tpu.solver.solver import Solver

        class Stub:
            pass

        stub = Stub()
        stub._snapshot_thread = None
        stub._snapshot_error = (700, OSError("disk full"))
        with pytest.raises(RuntimeError) as ei:
            Solver.wait_snapshots(stub, timeout=0.1)
        assert "iteration 700" in str(ei.value)
        assert stub._snapshot_error is None
