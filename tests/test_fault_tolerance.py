"""Survivable training (ISSUE 3, utils/resilience.py): verified atomic
snapshots, dispatch watchdog, supervised auto-resume, fault-injection
plane.

The acceptance bar: an injected feeder error, a corrupted snapshot, a
kill-mid-write, and a simulated dispatch stall must each end in a
successful auto-resume that is ITERATION-EXACT vs an uninterrupted run —
same final weight bits on CPU. The e2e scenarios drive the real CLI in
subprocesses (the kill/stall faults `os._exit`, so in-process is not an
option) over a tiny LMDB-backed net; unit tests cover the mechanism
pieces in-process.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import jax.numpy as jnp
import pytest

from caffe_mpi_tpu.proto import SolverParameter
from caffe_mpi_tpu.proto.config import NetParameter
from caffe_mpi_tpu.solver import Solver
from caffe_mpi_tpu.utils import resilience
from caffe_mpi_tpu.utils.resilience import (
    DispatchWatchdog, FaultPlane, atomic_output, gc_snapshots,
    iter_snapshot_manifests, retrying, verify_snapshot,
    write_snapshot_manifest)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# unit: atomic publication + manifests + GC
# ---------------------------------------------------------------------------

class TestAtomicManifests:
    def test_atomic_output_publishes_or_nothing(self, tmp_path):
        path = str(tmp_path / "f.bin")
        with atomic_output(path) as tmp:
            with open(tmp, "wb") as f:
                f.write(b"payload")
        assert open(path, "rb").read() == b"payload"
        with pytest.raises(ValueError):
            with atomic_output(path) as tmp:
                with open(tmp, "wb") as f:
                    f.write(b"half-")
                raise ValueError("writer died")
        # target untouched, no temp litter
        assert open(path, "rb").read() == b"payload"
        assert [n for n in os.listdir(tmp_path) if ".tmp" in n] == []

    def test_atomic_output_sweeps_stale_tmps(self, tmp_path):
        path = str(tmp_path / "f.bin")
        stale = f"{path}.tmp99999"
        open(stale, "wb").write(b"orphan from a killed writer")
        with atomic_output(path) as tmp:
            open(tmp, "wb").write(b"x")
        assert not os.path.exists(stale)

    def _fake_snapshot(self, tmp_path, it, payload=b"weights"):
        prefix = str(tmp_path / "s")
        model = f"{prefix}_iter_{it}.caffemodel"
        state = f"{prefix}_iter_{it}.solverstate"
        open(model, "wb").write(payload + str(it).encode())
        open(state, "wb").write(b"state" + str(it).encode())
        write_snapshot_manifest(state, it, {"model": model, "state": state})
        return model, state

    def test_manifest_verify_and_corruption(self, tmp_path):
        model, state = self._fake_snapshot(tmp_path, 4)
        (it, mpath), = iter_snapshot_manifests(str(tmp_path / "s"))
        assert it == 4
        doc = verify_snapshot(mpath)
        assert doc is not None and doc["state"] == os.path.abspath(state)
        # flip one byte -> crc mismatch -> None
        b = bytearray(open(model, "rb").read())
        b[len(b) // 2] ^= 0xFF
        open(model, "wb").write(bytes(b))
        assert verify_snapshot(mpath) is None
        # truncation (size mismatch) also detected
        model2, _ = self._fake_snapshot(tmp_path, 8)
        open(model2, "wb").write(b"w")
        (_, mpath2), _ = iter_snapshot_manifests(str(tmp_path / "s"))
        assert verify_snapshot(mpath2) is None

    def test_gc_never_deletes_newest_verified(self, tmp_path):
        prefix = str(tmp_path / "s")
        for it in (2, 4, 6, 8):
            self._fake_snapshot(tmp_path, it)
        # corrupt the newest two: the newest VERIFIED is iter 4
        for it in (6, 8):
            p = f"{prefix}_iter_{it}.caffemodel"
            open(p, "ab").write(b"rot")
        gc_snapshots(prefix, keep=2)
        remaining = {it for it, _ in iter_snapshot_manifests(prefix)}
        # keep window = {8, 6}; iter 4 survives as the newest verified;
        # iter 2 swept
        assert remaining == {8, 6, 4}
        gc_snapshots(prefix, keep=1)
        remaining = {it for it, _ in iter_snapshot_manifests(prefix)}
        assert 4 in remaining and 2 not in remaining


# ---------------------------------------------------------------------------
# unit: fault plane / watchdog / retry
# ---------------------------------------------------------------------------

class TestFaultPlane:
    def test_count_skip_arg(self):
        fp = FaultPlane()
        fp.configure("site:2:1:arg")
        assert fp.fire("site") is None          # skipped
        assert fp.fire("site") == "arg"         # 1st fire
        assert fp.fire("other") is None
        assert fp.fire("site") == "arg"         # 2nd fire
        assert fp.fire("site") is None          # exhausted
        assert fp.fire("site") is None

    def test_threshold_key(self):
        fp = FaultPlane()
        fp.configure("abort:1::9")
        assert fp.fire("abort", key=5) is None
        assert fp.fire("abort", key=9) == "9"
        assert fp.fire("abort", key=10) is None  # exhausted

    def test_once_dir_disables_across_processes(self, tmp_path):
        d = str(tmp_path)
        fp = FaultPlane()
        fp.configure("boom:1", once_dir=d)
        assert fp.fire("boom") == ""
        assert os.path.exists(os.path.join(d, "boom.done"))
        fp2 = FaultPlane()  # "the restarted process"
        fp2.configure("boom:1", once_dir=d)
        assert fp2.fire("boom") is None

    def test_zero_cost_when_off(self):
        fp = FaultPlane()
        fp.configure("")
        assert fp.fire("anything") is None


class TestWatchdogRetry:
    def test_watchdog_trips_on_stuck_section(self):
        trips = []
        wd = DispatchWatchdog(0.2, lambda label, el: trips.append(label),
                              poll=0.05, hard_exit=False)
        try:
            with wd.section("dispatch"):
                assert wd.tripped_event.wait(3.0)
        finally:
            wd.stop()
        assert trips == ["dispatch"]
        assert wd.tripped[0] == "dispatch" and wd.tripped[1] > 0.2

    def test_watchdog_quiet_on_fast_sections(self):
        wd = DispatchWatchdog(0.5, poll=0.02, hard_exit=False)
        try:
            for _ in range(5):
                with wd.section("dispatch"):
                    time.sleep(0.01)
            time.sleep(0.1)
            assert wd.tripped is None
        finally:
            wd.stop()

    def test_retrying_bounded(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"
        assert retrying(flaky, attempts=4, base_delay=0.001) == "ok"
        assert len(calls) == 3

        hard = []

        def always_fails():
            hard.append(1)
            raise OSError("hard")
        with pytest.raises(OSError, match="hard"):
            retrying(always_fails, attempts=3, base_delay=0.001)
        assert len(hard) == 3  # bounded, not infinite


# ---------------------------------------------------------------------------
# unit: feeder retry + feed-queue error context
# ---------------------------------------------------------------------------

class _TinyDataset:
    def __init__(self, n=8):
        self.n = n

    def __len__(self):
        return self.n

    def get(self, i):
        img = np.full((1, 4, 4), i, np.uint8)
        return img, i % 4


class TestFeederFaults:
    def test_transient_read_retries(self):
        from caffe_mpi_tpu.data.feeder import Feeder
        resilience.FAULTS.configure("feeder_read:2")
        try:
            f = Feeder(_TinyDataset(), None, 4, threads=1)
            batch = f._build_batch_inner(0)
            assert batch["data"].shape == (4, 1, 4, 4)
            f.close()
        finally:
            resilience.FAULTS.configure("")

    def test_persistent_read_surfaces(self):
        from caffe_mpi_tpu.data.feeder import Feeder
        resilience.FAULTS.configure("feeder_read:99")
        try:
            f = Feeder(_TinyDataset(), None, 4, threads=1)
            with pytest.raises(OSError, match="injected dataset read"):
                f._build_batch_inner(0)
            f.close()
        finally:
            resilience.FAULTS.configure("")

    def test_feed_queue_names_failing_chunk(self):
        from caffe_mpi_tpu.data.feeder import DeviceFeedQueue, FeedError

        def bad_feed(it):
            raise OSError(f"disk gone at micro-iter {it}")
        q = DeviceFeedQueue(bad_feed)
        try:
            with pytest.raises(FeedError, match=r"it0=6, k=3"):
                q.get(6, 3)
        finally:
            q.close()


# ---------------------------------------------------------------------------
# solver-level: verified snapshots, GC knob, corruption fallback
# ---------------------------------------------------------------------------

LSQ_NET = """
name: "lsq"
layer { name: "in" type: "Input" top: "x" top: "t"
        input_param { shape { dim: 4 dim: 3 } shape { dim: 4 dim: 1 } } }
layer { name: "ip" type: "InnerProduct" bottom: "x" top: "pred"
        inner_product_param { num_output: 1
          weight_filler { type: "gaussian" std: 1 } } }
layer { name: "loss" type: "EuclideanLoss" bottom: "pred" bottom: "t" top: "l" }
"""


def _make_solver(extra=""):
    sp = SolverParameter.from_text(
        f'base_lr: 0.1 max_iter: 50 lr_policy: "fixed" display: 0 '
        f'random_seed: 3\n{extra}')
    sp.net_param = NetParameter.from_text(LSQ_NET)
    return Solver(sp)


def _feeds(it):
    r = np.random.RandomState(it % 16)
    x = r.randn(4, 3).astype(np.float32)
    t = (x @ np.array([[1.0], [-2.0], [0.5]]) + 0.3).astype(np.float32)
    return {"x": jnp.asarray(x), "t": jnp.asarray(t)}


class TestSolverSnapshots:
    def test_snapshot_keep_gc_and_run_manifest(self, tmp_path):
        s = _make_solver("snapshot: 2 snapshot_keep: 2")
        s.sp.snapshot_prefix = str(tmp_path / "s")
        s.step(8, _feeds)
        s.close()
        its = [it for it, _ in iter_snapshot_manifests(str(tmp_path / "s"))]
        assert its == [8, 6]  # keep=2: older sets GC'd
        for _it, m in iter_snapshot_manifests(str(tmp_path / "s")):
            assert verify_snapshot(m) is not None
        assert not os.path.exists(tmp_path / "s_iter_2.caffemodel")
        run = resilience.read_run_manifest(str(tmp_path / "s"))
        assert run["iter"] == 8 and run["reason"] == "snapshot"
        assert run["last_snapshot_state"].endswith("s_iter_8.solverstate")

    def test_restore_rejects_corrupt_and_auto_falls_back(self, tmp_path):
        ref = _make_solver("snapshot: 2")
        ref.sp.snapshot_prefix = str(tmp_path / "s")
        ref.step(6, _feeds)
        ref.close()
        final_w = np.asarray(ref.params["ip"]["weight"])
        # corrupt the newest model file (post-manifest bitrot)
        p = tmp_path / "s_iter_6.caffemodel"
        b = bytearray(p.read_bytes())
        b[len(b) // 2] ^= 0xFF
        p.write_bytes(bytes(b))

        fresh = _make_solver()
        fresh.sp.snapshot_prefix = str(tmp_path / "s")
        with pytest.raises(resilience.SnapshotCorruptError):
            fresh.restore(str(tmp_path / "s_iter_6.solverstate"))
        # auto-resume skips the corrupt 6 and lands on the verified 4,
        # replays 4..6 and must match the uninterrupted run bit-exactly
        state = fresh.restore_auto()
        assert state.endswith("s_iter_4.solverstate")
        assert fresh.iter == 4
        fresh.step(2, _feeds)
        fresh.close()
        assert np.array_equal(np.asarray(fresh.params["ip"]["weight"]),
                              final_w)

    def test_restore_auto_handles_legacy_unmanifested(self, tmp_path):
        ref = _make_solver()
        ref.sp.snapshot_prefix = str(tmp_path / "s")
        ref.step(3, _feeds)
        ref.snapshot()
        ref.close()
        # simulate a pre-ISSUE-3 snapshot: drop the manifest sidecar
        os.unlink(tmp_path / "s_iter_3.manifest.json")
        fresh = _make_solver()
        fresh.sp.snapshot_prefix = str(tmp_path / "s")
        assert fresh.restore_auto().endswith("s_iter_3.solverstate")
        assert fresh.iter == 3
        fresh.close()

    def test_restore_auto_empty_is_fresh_start(self, tmp_path):
        s = _make_solver()
        s.sp.snapshot_prefix = str(tmp_path / "nothing" / "here")
        assert s.restore_auto() is None
        assert s.iter == 0
        s.close()


# ---------------------------------------------------------------------------
# e2e acceptance: CLI subprocesses, each fault ends in an auto-resume
# that is iteration-exact vs the uninterrupted baseline
# ---------------------------------------------------------------------------

def _build_workspace(root):
    """Tiny LMDB + prototxts shared by every scenario (snapshot prefix
    differs per scenario via -snapshot_prefix)."""
    from caffe_mpi_tpu.data.datasets import encode_datum
    from caffe_mpi_tpu.data.lmdb_io import write_lmdb
    os.makedirs(root, exist_ok=True)
    db = os.path.join(root, "train_lmdb")
    r = np.random.RandomState(7)
    write_lmdb(db, ((f"{i:08d}".encode(),
                     encode_datum(r.randint(0, 256, (1, 6, 6), np.uint8)
                                  .astype(np.uint8), int(i % 4)))
                    for i in range(16)))
    net = os.path.join(root, "net.prototxt")
    with open(net, "w") as f:
        f.write(f"""
name: "ftnet"
layer {{ name: "data" type: "Data" top: "data" top: "label"
        data_param {{ source: "{db}" batch_size: 4 backend: LMDB }} }}
layer {{ name: "ip" type: "InnerProduct" bottom: "data" top: "score"
        inner_product_param {{ num_output: 4
          weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "score"
        bottom: "label" top: "loss" }}
""")
    solver = os.path.join(root, "solver.prototxt")
    with open(solver, "w") as f:
        f.write(f'net: "{net}"\nbase_lr: 0.05 momentum: 0.9\n'
                f'lr_policy: "fixed" max_iter: 12 random_seed: 3\n'
                f'display: 0 snapshot: 4\n')
    return solver


def _run_cli(solver, prefix, *extra, faults="", faults_dir="",
             timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT,
               CAFFE_TPU_FAULTS=faults,
               CAFFE_TPU_FAULTS_DIR=faults_dir)
    env.pop("CAFFE_SUPERVISED_CHILD", None)
    cmd = [sys.executable, "-m", "caffe_mpi_tpu.tools.cli", "train",
           "-solver", solver, "-snapshot_prefix", prefix, *extra]
    return subprocess.run(cmd, env=env, cwd=_ROOT, timeout=timeout,
                          capture_output=True, text=True)


def _final_weights(prefix):
    from caffe_mpi_tpu.io import load_caffemodel
    path = f"{prefix}_iter_12.caffemodel"
    assert os.path.exists(path), f"missing final snapshot {path}"
    return load_caffemodel(path)


def _assert_bitwise_equal(got, want):
    assert set(got) == set(want)
    for lname in want:
        for a, b in zip(got[lname], want[lname]):
            assert np.array_equal(a, b), f"{lname}: weight bits differ"


@pytest.fixture(scope="module")
def ws(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("fault_tolerance"))
    solver = _build_workspace(root)
    prefix = os.path.join(root, "baseline", "s")
    r = _run_cli(solver, prefix)
    assert r.returncode == 0, r.stderr[-2000:]
    return {"root": root, "solver": solver,
            "baseline": _final_weights(prefix)}


def _scenario(ws, name, faults, *extra):
    root = ws["root"]
    prefix = os.path.join(root, name, "s")
    fdir = os.path.join(root, name + "_faults")
    os.makedirs(fdir, exist_ok=True)
    r = _run_cli(ws["solver"], prefix, *extra, faults=faults,
                 faults_dir=fdir)
    assert r.returncode == 0, \
        f"{name}: rc={r.returncode}\n{r.stdout[-1500:]}\n{r.stderr[-1500:]}"
    _assert_bitwise_equal(_final_weights(prefix), ws["baseline"])
    return r


class TestEndToEndRecovery:
    def test_transient_feeder_error_absorbed(self, ws):
        """2 injected read failures < the 4-attempt retry budget: the
        run completes with NO restart, iteration-exact."""
        r = _scenario(ws, "feed_transient", "feeder_read:2")
        assert "supervisor" not in r.stderr  # absorbed in-process

    def test_fatal_feeder_error_restarts(self, ws):
        """A sticky read failure (the dataset is gone, not one blip)
        exhausts the retry budget; the child dies, the supervisor
        restarts it from the newest verified snapshot, and the final
        bits match the uninterrupted run."""
        r = _scenario(ws, "feed_fatal", "feeder_read:-1",
                      "-max_restarts", "2")
        assert "restarting from the newest verified snapshot" in r.stderr

    def test_kill_mid_snapshot_write(self, ws):
        """Process dies INSIDE the snapshot-8 write (after the model
        file, before state+manifest; snapshot_sync pins the write to
        the iteration boundary): the half-written snapshot is invisible
        to resume, the previous one (iter 4) loads, and the replayed
        run is bit-exact."""
        r = _scenario(ws, "kill_mid_write",
                      "snapshot_sync:-1,snapshot_kill:1:1",
                      "-max_restarts", "2")
        assert "restarting from the newest verified snapshot" in r.stderr
        assert "Restored solver state" in r.stderr
        assert "s_iter_4.solverstate" in r.stderr

    def test_corrupted_snapshot_falls_back(self, ws):
        """Snapshot 8 is corrupted after its manifest lands (bitrot;
        snapshot_sync makes the write order deterministic); the child
        then dies at iter 10. Resume detects the crc mismatch, falls
        back to the verified iter-4 snapshot, and replays to an
        identical result."""
        r = _scenario(ws, "corrupt",
                      "snapshot_sync:-1,snapshot_corrupt:1:1,"
                      "train_abort:1:0:10", "-max_restarts", "2")
        assert "failed crc verification" in r.stderr
        assert "s_iter_4.solverstate" in r.stderr

    def test_dispatch_stall_watchdog_resume(self, ws):
        """A 12s stall inside a train dispatch vs a 3s watchdog
        deadline: the monitor journals the run state, hard-exits 86,
        and the supervisor auto-resumes to a bit-exact finish."""
        r = _scenario(ws, "stall", "dispatch_stall:1:6:12",
                      "-max_restarts", "2", "-watchdog_deadline", "3")
        assert "exceeded 3.0s deadline" in r.stderr
        assert "supervisor: child failed (watchdog)" in r.stderr
        # the watchdog journaled before dying
        run = resilience.read_run_manifest(
            os.path.join(ws["root"], "stall", "s"))
        assert run is not None  # rewritten by the recovered run
        fail_log = os.path.join(ws["root"], "stall", "s.failures.log")
        assert os.path.exists(fail_log)
        assert "watchdog" in open(fail_log).read()

    def test_crash_loop_guard_gives_up(self, ws):
        """Unrecoverable fault (refires every restart: no once-marker
        dir): the supervisor stops after N restarts, preserving the
        failure log, instead of looping forever."""
        root = ws["root"]
        prefix = os.path.join(root, "crashloop", "s")
        r = _run_cli(ws["solver"], prefix, "-max_restarts", "1",
                     faults="train_abort:99:0:2")  # no faults_dir
        assert r.returncode == resilience.EXIT_FAULT
        assert "crash-loop guard" in r.stderr
        log = prefix + ".failures.log"
        assert os.path.exists(log)
        assert len(open(log).read().splitlines()) >= 2
