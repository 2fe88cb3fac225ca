"""Multi-host training suite (jax.distributed over localhost, CPU).

The reference's multi-node path (MPI_Init + global NCCL communicator,
clusters.cpp:8-45, parallel.cpp:166-169) was only ever exercised by
actually running under mpirun — SURVEY §4 flags the missing fake-cluster
test as the gap this build closes. Two layers here:

1. (slow) 2-process DP/ZeRO math: REAL processes form a cluster and
   train through MeshPlan.shard_feeds's
   make_array_from_process_local_data branch; parameters must match a
   single-process run on the same global batches. Skips where the CPU
   backend cannot form multiprocess computations.
2. (tier-1, ISSUE 11) the ELASTIC runtime, which needs no multiprocess
   computations: 2-process wiring smokes (cluster formation, mesh
   shape, disjoint per-host Feeder striping, per-host quarantine
   journals merged by rank 0) and the host-kill acceptance — a
   `host_loss`-injected worker kill must end in a journaled exit-87 +
   coordinated supervised `--resume auto` restart whose final weights
   are BIT-IDENTICAL to an uninterrupted 2-process baseline
   (tools/multihost_smoke.py). Single-process tests hold the sharded
   (orbax) verified-snapshot scheme, bounded cluster init, and the
   heartbeat mechanism.
"""

import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)

NET = """
name: "mh_mlp"
layer { name: "in" type: "Input" top: "x" top: "t"
        input_param { shape { dim: 16 dim: 8 } shape { dim: 16 } } }
layer { name: "ip1" type: "InnerProduct" bottom: "x" top: "h"
        inner_product_param { num_output: 32 weight_filler { type: "xavier" } } }
layer { name: "r" type: "ReLU" bottom: "h" top: "h" }
layer { name: "ip2" type: "InnerProduct" bottom: "h" top: "y"
        inner_product_param { num_output: 4 weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "y" bottom: "t" top: "l" }
"""
SOLVER_TEXT = ('base_lr: 0.05 momentum: 0.9 lr_policy: "fixed" max_iter: 50 '
               'type: "SGD" random_seed: 7')
N_STEPS = 5
GLOBAL_BATCH = 16


def global_batches(n, seed=3):
    r = np.random.RandomState(seed)
    return [{"x": r.randn(GLOBAL_BATCH, 8).astype(np.float32),
             "t": r.randint(0, 4, GLOBAL_BATCH)} for _ in range(n)]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _run_cluster(tmp_path, mode):
    port = _free_port()
    out = tmp_path / "proc0_params.npz"
    # children set their own platform pins; don't let the suite's leak in
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.join(_HERE, "multihost_worker.py"),
             f"localhost:{port}", "2", str(i), str(out), mode],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for i in range(2)
    ]
    logs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-host worker timed out")
        logs.append(stdout)
    if any("Multiprocess computations aren't implemented" in l
           for l in logs):
        # this jaxlib's CPU backend cannot form a cross-process
        # computation at all (jax.distributed connects, but the first
        # collective device_put raises) — the test is unrunnable here,
        # not failing. Real multi-host coverage needs a TPU slice.
        pytest.skip("backend cannot run multiprocess computations "
                    "(CPU); multi-host DP needs real devices")
    for i, (p, l) in enumerate(zip(procs, logs)):
        assert p.returncode == 0, f"proc {i} failed:\n{l[-3000:]}"
    return out


def _single_process_reference():
    import jax.numpy as jnp
    from caffe_mpi_tpu.proto import NetParameter, SolverParameter
    from caffe_mpi_tpu.solver import Solver

    sp = SolverParameter.from_text(SOLVER_TEXT)
    sp.net_param = NetParameter.from_text(NET)
    solver = Solver(sp)
    data = global_batches(N_STEPS)
    solver.step(N_STEPS, lambda it: {
        "x": jnp.asarray(data[it]["x"]), "t": jnp.asarray(data[it]["t"])})
    return solver


@pytest.mark.slow
def test_two_process_zero1_with_collective_snapshot(tmp_path):
    """Multi-host ZeRO-1: slots span both processes; training matches
    single-process; snapshot's history gather runs the collective
    process_allgather path and rank 0's files parse + match."""
    out = _run_cluster(tmp_path, "zero")
    got = np.load(out)
    ref = _single_process_reference()
    np.testing.assert_allclose(got["ip1_w"],
                               np.asarray(ref.params["ip1"]["weight"]),
                               rtol=2e-4, atol=1e-6)
    from caffe_mpi_tpu.io import load_solverstate
    state = str(out) + f".snap_iter_{N_STEPS}.solverstate"
    assert os.path.exists(state)
    it, _learned, history, _cur = load_solverstate(state)
    assert it == N_STEPS
    assert len(history) == 4  # (w,b) x 2 layers, 1 SGD slot each
    # the allgathered ip1 weight history equals the single-process slot
    (ref_hist,) = ref.opt_state["ip1"]["weight"]
    ref_hist = np.asarray(ref_hist)
    np.testing.assert_allclose(history[0].reshape(ref_hist.shape), ref_hist,
                               rtol=2e-4, atol=1e-6)


@pytest.mark.slow
def test_two_process_dp_matches_single_process(tmp_path):
    out = _run_cluster(tmp_path, "dp")
    got = np.load(out)

    # single-process reference on the same global batches, in-suite
    solver = _single_process_reference()
    np.testing.assert_allclose(got["ip1_w"],
                               np.asarray(solver.params["ip1"]["weight"]),
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(got["ip2_w"],
                               np.asarray(solver.params["ip2"]["weight"]),
                               rtol=2e-4, atol=1e-6)


# ===========================================================================
# ISSUE 11 — elastic multi-host runtime (tier-1: no multiprocess
# computations needed)
# ===========================================================================

def _clean_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "CAFFE_TPU_FAULTS",
                        "CAFFE_TPU_FAULTS_DIR", "CAFFE_SUPERVISED_CHILD")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT, **extra)
    return env


class TestClusterWiring:
    """2-process wiring asserts: cluster formation through the hardened
    init, global mesh shape, disjoint per-host record striping over a
    real LMDB, per-host quarantine journals merged by rank 0 — all
    without a cross-process computation (the worker asserts; rank 0
    prints WIRING-OK)."""

    def _write_index_lmdb(self, path, n=16):
        from caffe_mpi_tpu.data.datasets import encode_datum
        from caffe_mpi_tpu.data.lmdb_io import write_lmdb
        write_lmdb(path, ((f"{i:08d}".encode(),
                           encode_datum(np.full((1, 6, 6), i, np.uint8),
                                        int(i % 4)))
                          for i in range(n)))

    def test_two_process_wiring(self, tmp_path):
        self._write_index_lmdb(str(tmp_path / "db"))
        port = _free_port()
        # one corrupt record INSIDE each rank's stripe (B=4, world=2:
        # rank 0 owns flats {0..3, 8..11}, rank 1 {4..7, 12..15})
        corrupt = {0: 1, 1: 5}
        procs, logs = [], []
        for i in range(2):
            env = _clean_env(
                CAFFE_TPU_FAULTS=f"record_corrupt:1:0:{corrupt[i]}",
                WIRING_CORRUPT_INDEX=str(corrupt[i]),
                WIRING_PEER_CORRUPT_INDEX=str(corrupt[1 - i]))
            procs.append(subprocess.Popen(
                [sys.executable,
                 os.path.join(_HERE, "multihost_wiring_worker.py"),
                 f"localhost:{port}", "2", str(i), str(tmp_path)],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True))
        for p in procs:
            try:
                out, _ = p.communicate(timeout=180)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                pytest.fail("wiring worker timed out")
            logs.append(out)
        for i, (p, l) in enumerate(zip(procs, logs)):
            assert p.returncode == 0, f"proc {i} failed:\n{l[-3000:]}"
        assert "WIRING-OK" in logs[0]


class TestElasticRecovery:
    """The ISSUE 11 acceptance bar: a 2-process CPU cluster survives a
    `host_loss`-injected worker kill — the survivor journals
    `host_lost` and exits 87 within host_deadline, both supervisors
    restart with `--resume auto`, the cluster re-forms, and the
    recovered run's final weights are bit-identical to an uninterrupted
    2-process baseline."""

    def test_host_loss_supervised_recovery(self, tmp_path):
        r = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "tools",
                                          "multihost_smoke.py"),
             "--json", "--workdir", str(tmp_path)],
            env=_clean_env(), cwd=_ROOT, timeout=560,
            capture_output=True, text=True)
        line = next((l for l in r.stdout.splitlines()
                     if l.startswith('{"multihost_smoke"')), None)
        assert line, f"no smoke report:\n{r.stdout[-2000:]}" \
                     f"\n{r.stderr[-2000:]}"
        rep = json.loads(line)["multihost_smoke"]
        assert r.returncode == 0, rep
        assert rep["baseline_rcs"] == [0, 0], rep
        assert rep["recovery_rcs"] == [0, 0], rep
        assert rep["host_loss_detected"], rep
        assert rep["coordinated_restart"], rep
        assert rep["weights_bitwise_equal"], rep
        # the survivor's journal recorded WHICH peer was lost before
        # the exit (the run journal is later rewritten by the recovered
        # run, so the forensic record is the supervisor failure log +
        # the worker stdout asserted inside the smoke); here assert the
        # on-disk artifacts the operator would read
        flog = tmp_path / "recovery" / "s.failures.log"
        assert flog.exists()
        assert "fault/cluster" in flog.read_text()


class TestShardedSnapshots:
    """Single-process half of the sharded-snapshot contract: per-shard
    crc manifests as the commit record, shard corruption detected and
    fallen back from, GC that sweeps whole .orbax dirs, legacy
    manifest-less dirs still resumable."""

    NET = """
    name: "lsq"
    layer { name: "in" type: "Input" top: "x" top: "t"
            input_param { shape { dim: 4 dim: 3 } shape { dim: 4 dim: 1 } } }
    layer { name: "ip" type: "InnerProduct" bottom: "x" top: "pred"
            inner_product_param { num_output: 1
              weight_filler { type: "gaussian" std: 1 } } }
    layer { name: "loss" type: "EuclideanLoss" bottom: "pred"
            bottom: "t" top: "l" }
    """

    def _solver(self, extra=""):
        from caffe_mpi_tpu.proto import SolverParameter
        from caffe_mpi_tpu.proto.config import NetParameter
        from caffe_mpi_tpu.solver import Solver
        sp = SolverParameter.from_text(
            'base_lr: 0.1 max_iter: 50 lr_policy: "fixed" display: 0 '
            f'random_seed: 3 snapshot_format: ORBAX\n{extra}')
        sp.net_param = NetParameter.from_text(self.NET)
        return Solver(sp)

    @staticmethod
    def _feeds(it):
        import jax.numpy as jnp
        r = np.random.RandomState(it % 16)
        x = r.randn(4, 3).astype(np.float32)
        t = (x @ np.array([[1.0], [-2.0], [0.5]]) + 0.3).astype(np.float32)
        return {"x": jnp.asarray(x), "t": jnp.asarray(t)}

    def test_shard_corruption_detected_and_fallen_back(self, tmp_path):
        """The `snapshot_shard_corrupt` site rots one shard of the
        iter-6 set AFTER its manifest lands; explicit restore must
        reject the set, restore_auto must land on the verified iter-4
        set, and the replay must be bit-exact vs uninterrupted."""
        from caffe_mpi_tpu.utils import resilience
        s = self._solver("snapshot: 2")
        s.sp.snapshot_prefix = str(tmp_path / "s")
        resilience.FAULTS.configure("snapshot_shard_corrupt:1:2")
        try:
            s.step(6, self._feeds)  # snapshots at 2, 4; corrupt fires at 6
        finally:
            resilience.FAULTS.configure("")
        s.close()
        final_w = np.asarray(s.params["ip"]["weight"])
        manifests = resilience.iter_snapshot_manifests(str(tmp_path / "s"))
        assert [it for it, _ in manifests] == [6, 4, 2]
        assert resilience.verify_snapshot(manifests[0][1]) is None  # rot
        assert resilience.verify_snapshot(manifests[1][1]) is not None

        fresh = self._solver()
        fresh.sp.snapshot_prefix = str(tmp_path / "s")
        with pytest.raises(resilience.SnapshotCorruptError):
            fresh.restore(str(tmp_path / "s_iter_6.orbax"))
        state = fresh.restore_auto()
        assert state.endswith("s_iter_4.orbax")
        assert fresh.iter == 4
        fresh.step(2, self._feeds)
        fresh.close()
        assert np.array_equal(np.asarray(fresh.params["ip"]["weight"]),
                              final_w)
        # the run journal's resume pointer names the .orbax set
        run = resilience.read_run_manifest(str(tmp_path / "s"))
        assert run["last_snapshot_state"].endswith(".orbax")

    def test_gc_sweeps_whole_orbax_dirs(self, tmp_path):
        """snapshot_keep GC on sharded sets removes the DIRECTORY (no
        leaked shards, no half-deleted set) and never the newest
        verified one."""
        from caffe_mpi_tpu.utils import resilience
        s = self._solver("snapshot: 2 snapshot_keep: 2")
        s.sp.snapshot_prefix = str(tmp_path / "s")
        s.step(6, self._feeds)
        s.close()
        names = sorted(os.listdir(tmp_path))
        assert "s_iter_2.orbax" not in names                # GC'd whole
        assert "s_iter_2.orbax.manifest.json" not in names  # + manifest
        assert {"s_iter_4.orbax", "s_iter_6.orbax"} <= set(names)
        # corrupt BOTH kept sets: the newest verified (none here) rule
        # falls back to refusing to delete what resume still needs
        for it, m in resilience.iter_snapshot_manifests(str(tmp_path / "s")):
            assert resilience.verify_snapshot(m) is not None

    def test_legacy_manifestless_orbax_resumes(self, tmp_path):
        from caffe_mpi_tpu.utils import resilience
        s = self._solver()
        s.sp.snapshot_prefix = str(tmp_path / "s")
        s.step(3, self._feeds)
        s.snapshot()
        s.close()
        # simulate a pre-ISSUE-11 native snapshot: no manifest sidecar
        os.unlink(tmp_path / "s_iter_3.orbax.manifest.json")
        fresh = self._solver()
        fresh.sp.snapshot_prefix = str(tmp_path / "s")
        state = fresh.restore_auto()
        assert state and state.endswith("s_iter_3.orbax")
        assert fresh.iter == 3
        fresh.close()


class TestClusterInit:
    """Bounded cluster formation: retry/backoff around
    jax.distributed.initialize, `coordinator_down` injection, and the
    CLI's journaled exit-87 conversion."""

    def test_retry_recovers_and_exhaustion_is_bounded(self, monkeypatch):
        import jax
        from caffe_mpi_tpu.parallel import mesh
        from caffe_mpi_tpu.utils import resilience
        calls = []
        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda **kw: calls.append(kw))
        monkeypatch.setattr(jax.distributed, "shutdown", lambda: None)
        monkeypatch.setattr(jax, "process_index", lambda: 0)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        resilience.FAULTS.configure("coordinator_down:2")
        try:
            mesh.init_distributed("localhost:1", 2, 0, attempts=4,
                                  base_delay=0.01)
        finally:
            resilience.FAULTS.configure("")
        assert len(calls) == 1  # two injected outages absorbed
        resilience.FAULTS.configure("coordinator_down:-1")  # sticky
        try:
            with pytest.raises(resilience.ClusterError,
                               match="after 3 attempt"):
                mesh.init_distributed("localhost:1", 2, 0, attempts=3,
                                      base_delay=0.01)
        finally:
            resilience.FAULTS.configure("")

    def test_resolve_cluster_validates(self):
        from caffe_mpi_tpu.parallel import mesh
        from caffe_mpi_tpu.utils import resilience
        world, _, _ = mesh.resolve_cluster(None, host_id=0)
        assert world <= 1  # env-less default: single host

        class SP:
            hosts = 2
            coordinator = ""
        with pytest.raises(resilience.ClusterError, match="coordinator"):
            mesh.resolve_cluster(SP(), host_id=0)
        SP.coordinator = "localhost:1"
        with pytest.raises(resilience.ClusterError, match="host id"):
            mesh.resolve_cluster(SP(), host_id=-1)
        assert mesh.resolve_cluster(SP(), host_id=1) == (
            2, "localhost:1", 1)

    def test_cli_exits_87_with_journal_on_cluster_failure(self, tmp_path):
        """`caffe train -hosts 2` against a coordinator that never
        answers (sticky coordinator_down) must journal
        cluster_init_failed and exit EXIT_CLUSTER — never hang."""
        from caffe_mpi_tpu.utils import resilience
        net = tmp_path / "net.prototxt"
        net.write_text(TestShardedSnapshots.NET)
        solver = tmp_path / "solver.prototxt"
        solver.write_text(f'net: "{net}"\nbase_lr: 0.1 max_iter: 4 '
                          f'lr_policy: "fixed" display: 0\n')
        prefix = str(tmp_path / "run" / "s")
        r = subprocess.run(
            [sys.executable, "-m", "caffe_mpi_tpu.tools.cli", "train",
             "-solver", str(solver), "-synthetic",
             "-snapshot_prefix", prefix, "-hosts", "2",
             "-coordinator", "localhost:1", "-host_id", "0"],
            env=_clean_env(CAFFE_TPU_FAULTS="coordinator_down:-1",
                           CAFFE_TPU_INIT_TIMEOUT="2"),
            cwd=_ROOT, timeout=120, capture_output=True, text=True)
        assert r.returncode == resilience.EXIT_CLUSTER, \
            r.stderr[-2000:]
        run = resilience.read_run_manifest(prefix)
        assert run is not None
        assert run["reason"] == "cluster_init_failed"
        assert run["exit_code"] == resilience.EXIT_CLUSTER


class TestHeartbeat:
    """Mechanism unit: loss detection, startup grace, farewell."""

    def _pair(self, tmp_path, deadline=0.3, **kw):
        from caffe_mpi_tpu.utils.resilience import (DirBeatTransport,
                                                    HostHeartbeat)
        t = DirBeatTransport(str(tmp_path))
        mk = lambda host: HostHeartbeat(t, host, 2, deadline,
                                        interval=0.05, grace=0.5,
                                        hard_exit=False, **kw)
        return mk(0), mk(1)

    def test_silent_peer_trips_within_deadline(self, tmp_path):
        lost = []
        a, b = self._pair(tmp_path)
        a.on_lost = lambda p, e: lost.append((p, e))
        for _ in range(6):
            a.tick()
            b.tick()
            time.sleep(0.05)
        assert a.beats_seen(1) > 0 and a.lost is None
        t0 = time.monotonic()
        while a.lost is None and time.monotonic() - t0 < 3:
            a.tick()  # b stopped beating
            time.sleep(0.03)
        assert a.lost is not None and a.lost[0] == 1
        assert lost and lost[0][0] == 1
        assert a.lost_event.is_set()
        # detection latency is deadline-bounded (plus one tick)
        assert time.monotonic() - t0 < 1.5

    def test_farewell_suppresses_mourning(self, tmp_path):
        a, b = self._pair(tmp_path, deadline=0.2)
        a.tick()
        b.tick()
        time.sleep(0.06)
        a.tick()
        b.farewell()  # clean departure, no more beats
        t0 = time.monotonic()
        while time.monotonic() - t0 < 1.0:
            a.tick()
            time.sleep(0.03)
        assert a.lost is None

    def test_startup_grace_tolerates_slow_peer(self, tmp_path):
        """A peer that has NEVER beaten gets deadline+grace (jit
        compile skew), not bare deadline."""
        a, _ = self._pair(tmp_path, deadline=0.1)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 0.4:  # > deadline, < grace
            a.tick()
            time.sleep(0.03)
        assert a.lost is None

    def test_dir_transport_survives_incarnation_restart(self, tmp_path):
        """A restarted publisher's seq-0 must read as an ADVANCE (not
        staleness), and a bye left by a PREVIOUS incarnation must not
        suppress mourning of the current one — the shared directory
        outlives process incarnations."""
        from caffe_mpi_tpu.utils.resilience import DirBeatTransport
        reader = DirBeatTransport(str(tmp_path))
        old = DirBeatTransport(str(tmp_path))
        for s in range(40):
            old.publish(1, s)
        assert reader.latest_seq(1) == 39
        old.farewell(1)  # stale clean-exit marker
        new = DirBeatTransport(str(tmp_path))  # the restarted worker
        new.publish(1, 0)
        assert reader.latest_seq(1) > 39  # new incarnation advances
        assert not reader.is_bye(1)       # old bye cannot silence it
        new.farewell(1)
        assert reader.is_bye(1)           # its OWN bye still counts


class TestQuarantineMerge:
    def test_merge_dedups_and_sorts(self, tmp_path):
        from caffe_mpi_tpu.utils import resilience
        prefix = str(tmp_path / "s")
        assert resilience.quarantine_journal_path(prefix) \
            == prefix + ".quarantine.json"
        assert resilience.quarantine_journal_path(prefix, 1, 2) \
            == prefix + ".quarantine.r1.json"
        ent = lambda i: {"source": "db", "index": i, "key": "",
                         "substitute": i + 1, "reason": "crc", "time": 0}
        for rank, idxs in ((0, [3, 7]), (1, [7, 12])):
            with open(resilience.quarantine_journal_path(
                    prefix, rank, 2), "w") as f:
                json.dump({"schema": 1,
                           "records": [ent(i) for i in idxs]}, f)
        n = resilience.merge_quarantine_journals(prefix)
        assert n == 3  # 7 deduped
        doc = json.load(open(prefix + ".quarantine.json"))
        assert [e["index"] for e in doc["records"]] == [3, 7, 12]
        assert len(doc["merged_from"]) == 2

    def test_merge_noop_single_host(self, tmp_path):
        from caffe_mpi_tpu.utils import resilience
        assert resilience.merge_quarantine_journals(
            str(tmp_path / "s")) == 0
        assert not os.path.exists(tmp_path / "s.quarantine.json")
