"""Self-healing training (ISSUE 4): on-device non-finite guard,
divergence rewind, and the data-integrity plane.

The acceptance bars:
- guard OFF (default): nothing changes — covered implicitly by every
  pre-existing solver test;
- guard ON, clean data: training equals guard-off on CPU — bitwise, on
  one device and under a mesh (the guard lives in a lax.cond branch,
  see solver._iteration_fn);
- injected NaNs: the bad step is skipped on device (params/momentum
  unchanged), M consecutive skips exit 88, and the supervised rewind
  resumes iteration-exact vs an uninterrupted clean run;
- corrupt records: crc32c is verified on the DB read path, corrupt
  records quarantine with a journal, and a replay makes identical
  substitution decisions (same final weight bits).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from caffe_mpi_tpu.proto import SolverParameter
from caffe_mpi_tpu.proto.config import NetParameter
from caffe_mpi_tpu.solver import Solver
from caffe_mpi_tpu.utils import resilience

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LSQ_NET = """
name: "lsq"
layer { name: "in" type: "Input" top: "x" top: "t"
        input_param { shape { dim: 8 dim: 3 } shape { dim: 8 dim: 1 } } }
layer { name: "ip" type: "InnerProduct" bottom: "x" top: "pred"
        inner_product_param { num_output: 1
          weight_filler { type: "gaussian" std: 1 } } }
layer { name: "loss" type: "EuclideanLoss" bottom: "pred" bottom: "t" top: "l" }
"""


def make_solver(extra="", mesh=None):
    sp = SolverParameter.from_text(
        f'base_lr: 0.1 max_iter: 1000 lr_policy: "fixed" display: 0 '
        f'momentum: 0.9 random_seed: 3\n{extra}')
    sp.net_param = NetParameter.from_text(LSQ_NET)
    return Solver(sp, mesh=mesh)


def lsq_data(n=32):
    r = np.random.RandomState(1)
    out = []
    for _ in range(n):
        x = r.randn(8, 3).astype(np.float32)
        t = (x @ np.array([[1.0], [-2.0], [0.5]]) + 0.3).astype(np.float32)
        out.append({"x": x, "t": t})
    return out


def assert_bitwise_state(a: Solver, b: Solver):
    """Params and optimizer slots of `a` and `b` agree bitwise."""
    def same(x, y, what):
        assert np.array_equal(np.asarray(x), np.asarray(y)), f"{what} differ"
    for ln in a.params:
        for pn in a.params[ln]:
            same(a.params[ln][pn], b.params[ln][pn], f"params {ln}/{pn}")
    for ln in a.opt_state:
        for pn in a.opt_state[ln]:
            for si, (sa, sb) in enumerate(zip(a.opt_state[ln][pn],
                                              b.opt_state[ln][pn])):
                same(sa, sb, f"opt {ln}/{pn}[{si}]")


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    resilience.FAULTS.configure("")


# ---------------------------------------------------------------------------
# guard-on == guard-off, bitwise, clean data
# ---------------------------------------------------------------------------

class TestGuardEquivalence:
    def test_bitwise_equal_clean_data(self):
        """Guard on against guard off are two different programs and
        share no rounding contract: bitwise is for one program run twice
        or resumed. The one-iteration program happens to round alike on
        this jax's CPU backend and is held to it."""
        data = lsq_data()
        feed = lambda it: data[it % 32]
        a = make_solver()
        b = make_solver("train_guard: true")
        a.step(33, feed)
        b.step(33, feed)
        assert_bitwise_state(a, b)
        assert b.skipped_steps == 0
        # zero extra dispatches: the guard rides inside the programs
        assert b.dispatch_count == a.dispatch_count
        assert b.guard_sync_count > 0

    def test_bitwise_equal_under_mesh(self):
        from caffe_mpi_tpu.parallel import MeshPlan
        data = lsq_data()
        feed = lambda it: data[it % 32]
        a = make_solver(mesh=MeshPlan.data_parallel())
        b = make_solver("train_guard: true", mesh=MeshPlan.data_parallel())
        a.step(9, feed)
        b.step(9, feed)
        assert_bitwise_state(a, b)
        assert b.skipped_steps == 0

    def test_guard_rejects_gpipe(self):
        with pytest.raises(ValueError, match="train_guard.*gpipe"):
            sp = SolverParameter.from_text(
                'base_lr: 0.1 max_iter: 10 lr_policy: "fixed" '
                'train_guard: true')
            sp.net_param = NetParameter.from_text(LSQ_NET)
            Solver(sp, gpipe={"stages": 1, "micro": 1})


# ---------------------------------------------------------------------------
# skip-step semantics + divergence policy (in-process)
# ---------------------------------------------------------------------------

class TestSkipStep:
    def test_nan_step_skipped_params_unchanged(self):
        data = lsq_data()
        feed = lambda it: data[it % 32]
        resilience.FAULTS.configure("nan_grad:1:0:5")
        s = make_solver("train_guard: true guard_max_skips: 0")
        s.step(5, feed)
        w5 = np.asarray(s.params["ip"]["weight"]).copy()
        h5 = np.asarray(s.opt_state["ip"]["weight"][0]).copy()
        s.step(1, feed)  # iteration 5: poisoned -> skipped on device
        assert s.skipped_steps == 1
        assert np.array_equal(np.asarray(s.params["ip"]["weight"]), w5)
        assert np.array_equal(np.asarray(s.opt_state["ip"]["weight"][0]),
                              h5)
        # training continues and the consecutive counter resets
        s.step(4, feed)
        assert s.skipped_steps == 1

    def test_skip_then_recover_matches_freeze(self, tmp_path):
        """A skipped iteration is a no-op: the guarded run equals a
        run that never saw the bad iteration's update (same params
        before and after the skip)."""
        data = lsq_data()
        feed = lambda it: data[it % 32]
        resilience.FAULTS.configure("nan_grad:1:0:3")
        g = make_solver("train_guard: true")
        g.step(4, feed)  # iterations 0..3; 3 skipped on device
        assert g.skipped_steps == 1
        resilience.FAULTS.configure("")
        clean = make_solver("train_guard: true")
        clean.step(3, feed)  # clean run stopped before the bad iter
        assert_bitwise_state(g, clean)

    def test_consecutive_skips_raise_numeric_anomaly(self, tmp_path):
        data = lsq_data()
        feed = lambda it: data[it % 32]
        resilience.FAULTS.configure("nan_grad:3:0:2")
        s = make_solver("train_guard: true guard_max_skips: 3")
        s.sp.snapshot_prefix = str(tmp_path / "s")
        with pytest.raises(resilience.NumericAnomalyError) as ei:
            s.step(10, feed)
        assert ei.value.consec == 3
        run = resilience.read_run_manifest(str(tmp_path / "s"))
        assert run["reason"] == "numeric_anomaly"
        assert run["consec_skips"] == 3
        assert run["exit_code"] == resilience.EXIT_NUMERIC == 88

    def test_mid_chunk_burst_still_trips_policy(self, tmp_path):
        """A >=M consecutive burst that RECOVERS before the host's
        rate-limited check must still exit 88: `consec` has reset by the
        time the host looks, so the carry also tracks the longest burst seen
        (max_consec, monotone over the run — sound because reaching M
        always exits)."""
        data = lsq_data()
        feed = lambda it: data[it % 32]
        resilience.FAULTS.configure("nan_grad:3:0:2")  # iters 2,3,4 bad
        s = make_solver("train_guard: true guard_max_skips: 3")
        s.sp.snapshot_prefix = str(tmp_path / "s")
        with pytest.raises(resilience.NumericAnomalyError) as ei:
            s.step(10, feed)  # one check, at the end; burst ends at iter 5
        assert ei.value.consec == 3

    def test_divergence_blocks_snapshot_at_its_boundary(self, tmp_path):
        """A burst reaching M just before a snapshot boundary must
        raise BEFORE that snapshot is written: the deferred check is
        drained ahead of snapshot(), otherwise the rewind target would
        seal the skipped iterations and recovery would not be
        iteration-exact (bad iters 6,7 with snapshot 4: the iter-8
        snapshot must not exist)."""
        from caffe_mpi_tpu.utils.resilience import iter_snapshot_manifests
        data = lsq_data()
        feed = lambda it: data[it % 32]
        resilience.FAULTS.configure("nan_grad:2:0:6")
        s = make_solver("train_guard: true guard_max_skips: 2 "
                        "snapshot: 4")
        s.sp.snapshot_prefix = str(tmp_path / "s")
        with pytest.raises(resilience.NumericAnomalyError):
            s.step(12, feed)
        s.close()
        its = [it for it, _ in iter_snapshot_manifests(str(tmp_path / "s"))]
        assert its == [4], its  # iter-8 snapshot was NOT written

    def test_loss_spike_detector(self):
        data = lsq_data()
        feed = lambda it: data[it % 32]
        resilience.FAULTS.configure("loss_spike:1:0:6")
        s = make_solver("train_guard: true guard_loss_spike: 3.0 "
                        "guard_max_skips: 0")
        s.step(10, feed)
        assert s.skipped_steps == 1  # finite but 1e6x the EMA: skipped


# ---------------------------------------------------------------------------
# data-integrity plane (in-process units)
# ---------------------------------------------------------------------------

def _write_datum_lmdb(path, n=16, shape=(1, 6, 6)):
    from caffe_mpi_tpu.data.datasets import encode_datum
    from caffe_mpi_tpu.data.lmdb_io import write_lmdb
    r = np.random.RandomState(7)
    write_lmdb(path, ((f"{i:08d}".encode(),
                       encode_datum(r.randint(0, 256, shape)
                                    .astype(np.uint8), int(i % 4)))
                      for i in range(n)))
    return path


class TestDataIntegrity:
    def test_lmdb_sidecar_written_and_verified(self, tmp_path):
        from caffe_mpi_tpu.data.datasets import LMDBDataset
        from caffe_mpi_tpu.data.lmdb_io import read_crc_sidecar
        db = _write_datum_lmdb(str(tmp_path / "db"))
        assert os.path.exists(tmp_path / "db" / "data.mdb.crc32c")
        crcs = read_crc_sidecar(db)
        assert crcs is not None and len(crcs) == 16
        ds = LMDBDataset(db)
        assert ds._crcs is not None
        img, label = ds.get(3)
        assert img.shape == (1, 6, 6) and label == 3

    def test_on_disk_bitrot_detected(self, tmp_path):
        """Real bitrot: flip one byte of record 5's value bytes inside
        data.mdb — only that record must fail, with a crc mismatch."""
        from caffe_mpi_tpu.data.datasets import LMDBDataset
        db = _write_datum_lmdb(str(tmp_path / "db"))
        ds = LMDBDataset(db)
        # locate the record's unique value bytes in the data file
        from caffe_mpi_tpu.data.lmdb_io import LMDBReader
        rd = LMDBReader(db)
        val = rd.get(ds.keys[5])
        rd.close()
        data_path = os.path.join(db, "data.mdb")
        blob = bytearray(open(data_path, "rb").read())
        at = bytes(blob).find(val)
        assert at > 0
        blob[at + len(val) // 2] ^= 0xFF
        open(data_path, "wb").write(bytes(blob))
        ds2 = LMDBDataset(db)
        with pytest.raises(resilience.RecordIntegrityError,
                           match="crc32c mismatch"):
            ds2.get(5)
        ds2.get(4)  # neighbors unaffected
        ds2.get(6)

    def test_rotten_sidecar_is_ignored_not_fatal(self, tmp_path):
        from caffe_mpi_tpu.data.datasets import LMDBDataset
        db = _write_datum_lmdb(str(tmp_path / "db"))
        side = os.path.join(db, "data.mdb.crc32c")
        blob = bytearray(open(side, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        open(side, "wb").write(bytes(blob))
        ds = LMDBDataset(db)  # warns, loads unverified
        assert ds._crcs is None
        ds.get(5)

    def test_leveldb_block_crc_verified(self, tmp_path):
        from caffe_mpi_tpu.data.datasets import LevelDBDataset, \
            encode_datum
        from caffe_mpi_tpu.data.leveldb_io import (LevelDBError,
                                                   write_leveldb)
        r = np.random.RandomState(7)
        items = [(f"{i:08d}".encode(),
                  encode_datum(r.randint(0, 256, (1, 6, 6))
                               .astype(np.uint8), i % 4))
                 for i in range(16)]
        db = str(tmp_path / "ldb")
        write_leveldb(db, items)
        ds = LevelDBDataset(db)
        ds.get(3)
        # flip a byte inside the first data block: the reader's
        # open-time index build re-reads every block, so format-level
        # rot is a hard, named failure at open
        p = os.path.join(db, "000005.ldb")
        blob = bytearray(open(p, "rb").read())
        blob[50] ^= 0xFF
        open(p, "wb").write(bytes(blob))
        with pytest.raises(LevelDBError, match="crc32c"):
            LevelDBDataset(db)

    def test_feeder_quarantines_deterministically(self, tmp_path):
        from caffe_mpi_tpu.data.datasets import LMDBDataset
        from caffe_mpi_tpu.data.feeder import Feeder
        db = _write_datum_lmdb(str(tmp_path / "db"))
        resilience.FAULTS.configure("record_corrupt:1:0:5")
        resilience.QUARANTINE.configure(str(tmp_path / "q.json"))
        try:
            ds = LMDBDataset(db)
            f = Feeder(ds, None, 4, threads=1)
            batch1 = f._build_batch_inner(1)  # records 4..7: 5 is rot
            batch2 = f._build_batch_inner(1)  # replay: same decision
            np.testing.assert_array_equal(batch1["data"], batch2["data"])
            # the substitute is the next healthy record by index
            img6, _ = ds.get(6)
            np.testing.assert_array_equal(
                np.asarray(batch1["data"][1]), img6.astype(np.float32))
            doc = json.load(open(tmp_path / "q.json"))
            assert [e["index"] for e in doc["records"]] == [5]
            assert doc["records"][0]["substitute"] == 6
            f.close()
        finally:
            resilience.QUARANTINE.configure(None)

    def test_record_decode_quarantines_without_sidecar(self, tmp_path):
        """Truncated record on a sidecar-less (reference-written) DB:
        no crc to compare, but the Datum parse fails and quarantines
        the same way."""
        from caffe_mpi_tpu.data.datasets import LMDBDataset, encode_datum
        from caffe_mpi_tpu.data.lmdb_io import write_lmdb
        r = np.random.RandomState(7)
        db = str(tmp_path / "db")
        write_lmdb(db, ((f"{i:08d}".encode(),
                         encode_datum(r.randint(0, 256, (1, 6, 6))
                                      .astype(np.uint8), i % 4))
                        for i in range(16)), integrity=False)
        resilience.FAULTS.configure("record_decode:1:0:5")
        ds = LMDBDataset(db)
        assert ds._crcs is None
        with pytest.raises(resilience.RecordIntegrityError,
                           match="undecodable Datum"):
            ds.get(5)
        ds.get(4)

    def test_systematic_corruption_is_hard_failure(self, tmp_path):
        from caffe_mpi_tpu.data.datasets import LMDBDataset
        from caffe_mpi_tpu.data.feeder import Feeder
        db = _write_datum_lmdb(str(tmp_path / "db"))
        # every record rotten: the probe window exhausts -> named error
        resilience.FAULTS.configure("record_corrupt:16:0:0")
        ds = LMDBDataset(db)
        f = Feeder(ds, None, 4, threads=1)
        with pytest.raises(resilience.DataIntegrityError,
                           match="systematic"):
            f._build_batch_inner(0)
        f.close()


# ---------------------------------------------------------------------------
# supervisor anomaly routing (tiny shell children, no jax)
# ---------------------------------------------------------------------------

class TestSuperviseAnomalyRouting:
    def _mk_child(self, tmp_path):
        """Exits 88 on the first run, 0 once '-lr_scale' is passed."""
        script = tmp_path / "child.sh"
        script.write_text(
            '#!/bin/sh\nfor a in "$@"; do\n'
            '  [ "$a" = "-lr_scale" ] && exit 0\ndone\nexit 88\n')
        script.chmod(0o755)
        return str(script)

    def test_rewind_lr_appends_lr_scale(self, tmp_path):
        child = self._mk_child(tmp_path)
        rc = resilience.supervise(
            [child], [child, "-resume", "auto"], 2,
            failure_log=str(tmp_path / "f.log"),
            anomaly_action="rewind_lr", anomaly_lr_mult=0.1,
            backoff_base=0.01)
        assert rc == 0  # restart carried -lr_scale -> child succeeded
        assert "numeric divergence" in (tmp_path / "f.log").read_text()

    def test_plain_rewind_never_scales_lr(self, tmp_path):
        child = self._mk_child(tmp_path)
        rc = resilience.supervise(
            [child], [child, "-resume", "auto"], 1,
            failure_log=str(tmp_path / "f.log"),
            anomaly_action="rewind", backoff_base=0.01)
        # without -lr_scale the child keeps exiting 88: crash-loop guard
        assert rc == resilience.EXIT_NUMERIC


# ---------------------------------------------------------------------------
# e2e acceptance: CLI subprocesses
# ---------------------------------------------------------------------------

def _build_workspace(root):
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
    # use_gpu_transform: false => float host-transform feeds, which the
    # nan_grad/loss_spike sites can poison (the uint8 device-transform
    # staging path has no float leaf to NaN)
    with open(net, "w") as f:
        f.write(f"""
name: "sgnet"
layer {{ name: "data" type: "Data" top: "data" top: "label"
        transform_param {{ use_gpu_transform: false }}
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
    root = str(tmp_path_factory.mktemp("train_guard"))
    solver = _build_workspace(root)
    prefix = os.path.join(root, "baseline", "s")
    r = _run_cli(solver, prefix, "-train_guard")
    assert r.returncode == 0, r.stderr[-2000:]
    return {"root": root, "solver": solver,
            "baseline": _final_weights(prefix)}


class TestEndToEndSelfHealing:
    def test_nan_divergence_exit88_supervised_rewind(self, ws):
        """Iterations 5-6 NaN-poisoned, guard_max_skips 2: the child
        journals the anomaly and exits 88 BEFORE the iter-8 snapshot
        can capture the stalled state; the supervisor rewinds to the
        verified iter-4 snapshot; the fault's done-marker keeps the
        replay clean, so the recovered run is iteration-exact vs the
        uninterrupted baseline."""
        root = ws["root"]
        prefix = os.path.join(root, "nan_rewind", "s")
        fdir = os.path.join(root, "nan_rewind_faults")
        os.makedirs(fdir, exist_ok=True)
        r = _run_cli(ws["solver"], prefix, "-train_guard",
                     "-guard_max_skips", "2", "-max_restarts", "2",
                     faults="nan_grad:2:0:5", faults_dir=fdir)
        assert r.returncode == 0, \
            f"rc={r.returncode}\n{r.stdout[-1500:]}\n{r.stderr[-1500:]}"
        assert "exiting 88" in r.stderr
        assert "numeric divergence" in r.stderr
        assert "rewinding to the newest verified snapshot" in r.stderr
        assert "s_iter_4.solverstate" in r.stderr
        _assert_bitwise_equal(_final_weights(prefix), ws["baseline"])

    def test_anomaly_action_abort(self, ws):
        """anomaly_action abort: divergence is fatal — exit 88 with no
        restart (no faults_dir, so a restart would just re-diverge)."""
        root = ws["root"]
        prefix = os.path.join(root, "abort", "s")
        r = _run_cli(ws["solver"], prefix, "-train_guard",
                     "-guard_max_skips", "2", "-max_restarts", "2",
                     "-anomaly_action", "abort",
                     faults="nan_grad:2:0:5")
        assert r.returncode == resilience.EXIT_NUMERIC, r.stderr[-1500:]
        assert "anomaly_action 'abort'" in r.stderr
        assert "rewinding" not in r.stderr

    def test_corrupt_record_quarantine_replay_identical(self, ws):
        """Record 9 rots (durably — real bitrot survives restarts;
        index 9 because the net-build shape probe samples records 0-8
        and 15, and a corrupt PROBE record is a hard failure at open
        by design): both runs complete, journal identical substitution
        decisions, and produce identical final weights — quarantine is
        replay-deterministic."""
        root = ws["root"]
        runs = []
        for tag in ("q1", "q2"):
            prefix = os.path.join(root, tag, "s")
            r = _run_cli(ws["solver"], prefix, "-train_guard",
                         faults="record_corrupt:1:0:9")
            assert r.returncode == 0, r.stderr[-1500:]
            assert "quarantined record 9" in r.stderr
            q = json.load(open(prefix + ".quarantine.json"))
            runs.append((_final_weights(prefix), [
                (e["index"], e["substitute"], e["reason"])
                for e in q["records"]]))
        _assert_bitwise_equal(runs[0][0], runs[1][0])
        assert runs[0][1] == runs[1][1] == [
            (9, 10, runs[0][1][0][2])]
