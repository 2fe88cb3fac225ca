"""Persistent AOT program bank (ISSUE 17): zero-compile warm starts,
verified-atomic entry publication, corruption/fingerprint fallback, and
the netshape-planned admission path.

Reference: the reference deployment (caffe.cpp:291, classification.cpp)
has no compilation artifact to persist; this plane is TPU-native. The
behavior baseline is PR 7's zero-recompile invariant — extended here to
`compile_count == bank_misses` (unconditional) and `compile_count +
bank_hits == warmed_buckets` — plus PR 3's verified-atomic manifest
semantics applied to one standalone artifact per bucket program.
"""

import glob
import os
import threading

import numpy as np
import pytest

import caffe_mpi_tpu.pycaffe as caffe
from caffe_mpi_tpu.proto.config import NetParameter
from caffe_mpi_tpu.serving import BankStats, ProgramBank, ServingEngine
from caffe_mpi_tpu.serving.plan import plan_admission, plan_model
from caffe_mpi_tpu.serving.program_bank import fingerprint
from caffe_mpi_tpu.utils import resilience
from caffe_mpi_tpu.utils.resilience import (FAULTS, verify_file_manifest,
                                            write_file_manifest)

TOY_NET = """
name: "toy"
layer {{ name: "data" type: "Input" top: "data"
        input_param {{ shape {{ dim: {batch} dim: 3 dim: 8 dim: 8 }} }} }}
layer {{ name: "ip" type: "InnerProduct" bottom: "data" top: "score"
        inner_product_param {{ num_output: 5
          weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "prob" type: "Softmax" bottom: "score" top: "prob" }}
"""


def write_toy(tmp_path, batch=8, name="deploy.prototxt"):
    model = tmp_path / name
    model.write_text(TOY_NET.format(batch=batch))
    net = caffe.Net(str(model), caffe.TEST)
    weights = str(tmp_path / (name + ".caffemodel"))
    net.save(weights)
    return str(model), weights


def imgs(n, seed=0, hw=(8, 8)):
    r = np.random.RandomState(seed)
    return [r.rand(*hw, 3).astype(np.float32) for _ in range(n)]


def start(bank_dir, model, weights, **kw):
    eng = ServingEngine(window_ms=0,
                        program_bank=str(bank_dir) if bank_dir else None,
                        **kw)
    eng.load_model("m", model, weights)
    return eng


def bank_stats(eng):
    return eng.stats()["bank"]


# ---------------------------------------------------------------------------
# the invariant, bank off and on


class TestInvariant:
    def test_bank_off_misses_equal_compiles(self, tmp_path):
        model, weights = write_toy(tmp_path)
        eng = start(None, model, weights)
        try:
            st = bank_stats(eng)
            assert not st["enabled"]
            assert st["misses"] == eng.compile_count == eng.warmed_buckets
            assert st["hits"] == st["stores"] == 0
            ok, doc = eng.ready()
            assert ok and doc["bank_misses"] == eng.compile_count
        finally:
            eng.close()

    def test_warm_start_zero_compiles_bitwise(self, tmp_path):
        model, weights = write_toy(tmp_path)
        bank = tmp_path / "bank"
        cold = start(bank, model, weights)
        try:
            st = bank_stats(cold)
            assert st["enabled"] and st["path"] == str(bank)
            assert cold.compile_count == st["misses"] == cold.warmed_buckets
            assert st["stores"] == cold.warmed_buckets
            assert st["cold_start_ms"] > 0
            ref = cold.classify("m", imgs(5, seed=3))
        finally:
            cold.close()
        warm = start(bank, model, weights)
        try:
            st = bank_stats(warm)
            assert warm.compile_count == 0
            assert st["misses"] == 0
            assert st["hits"] == warm.warmed_buckets
            ok, doc = warm.ready()
            assert ok and doc["bank_hits"] == warm.warmed_buckets
            # the deserialized program is the stored XLA program, loaded
            # onto the one device it was compiled for whatever the process
            # sees (8 virtual devices here, 4 chips on a v5e host): scores
            # on the same inputs + weights are bitwise-identical
            out = warm.classify("m", imgs(5, seed=3))
            assert np.array_equal(np.asarray(ref), np.asarray(out))
            # warm events carry the per-bucket breakdown
            for ev in st["warm"]["m"]:
                assert ev["source"] == "bank"
                assert ev["compile_ms"] == 0.0
                assert ev["deserialize_ms"] > 0
        finally:
            warm.close()

    def test_repopulated_bank_serves_next_engine(self, tmp_path):
        model, weights = write_toy(tmp_path)
        bank = tmp_path / "bank"
        start(bank, model, weights).close()
        # wipe ONE entry: the next engine misses it, recompiles it, and
        # repopulates — the engine after that is fully warm again
        victim = sorted(glob.glob(str(bank / "*.xpb")))[0]
        os.remove(victim)
        os.remove(victim + ".manifest.json")
        mid = start(bank, model, weights)
        try:
            st = bank_stats(mid)
            assert mid.compile_count == st["misses"] == 1
            assert st["hits"] == mid.warmed_buckets - 1
            assert st["stores"] == 1
        finally:
            mid.close()
        warm = start(bank, model, weights)
        try:
            assert warm.compile_count == 0
            assert bank_stats(warm)["hits"] == warm.warmed_buckets
        finally:
            warm.close()


# ---------------------------------------------------------------------------
# corruption: every broken-entry shape is a counted miss, never a crash


class TestCorruption:
    def test_truncated_entry_rejected_and_repopulated(self, tmp_path):
        model, weights = write_toy(tmp_path)
        bank = tmp_path / "bank"
        start(bank, model, weights).close()
        victim = sorted(glob.glob(str(bank / "*.xpb")))[0]
        blob = open(victim, "rb").read()
        with open(victim, "wb") as f:
            f.write(blob[:len(blob) // 2])  # torn write
        eng = start(bank, model, weights)
        try:
            st = bank_stats(eng)
            assert eng.compile_count == st["misses"] == 1
            assert st["verify_rejects"] == 1
            assert st["stores"] == 1  # repopulated
            eng.classify("m", imgs(2))
        finally:
            eng.close()
        # the repopulated entry round-trips
        warm = start(bank, model, weights)
        try:
            assert warm.compile_count == 0
        finally:
            warm.close()

    def test_bank_corrupt_fault_site(self, tmp_path):
        # the registered site flips a payload byte AFTER the manifest
        # committed — the bitrot shape the crc32c verify exists for
        model, weights = write_toy(tmp_path)
        bank = tmp_path / "bank"
        FAULTS.configure("bank_corrupt:1")
        try:
            start(bank, model, weights).close()
        finally:
            FAULTS.configure("")
        eng = start(bank, model, weights)
        try:
            st = bank_stats(eng)
            assert st["verify_rejects"] == 1
            assert eng.compile_count == st["misses"] == 1
            assert st["hits"] == eng.warmed_buckets - 1
            ok, _ = eng.ready()
            assert ok
        finally:
            eng.close()

    def test_garbage_payload_with_valid_manifest(self, tmp_path):
        # a verified entry that still fails to unpickle/deserialize must
        # count deserialize_failures and recompile, never crash
        model, weights = write_toy(tmp_path)
        bank = tmp_path / "bank"
        start(bank, model, weights).close()
        victim = sorted(glob.glob(str(bank / "*.xpb")))[0]
        with open(victim, "wb") as f:
            f.write(b"not a pickled executable")
        write_file_manifest(victim)  # re-commit: crc now matches garbage
        eng = start(bank, model, weights)
        try:
            st = bank_stats(eng)
            assert st["deserialize_failures"] == 1
            assert st["verify_rejects"] == 0
            assert eng.compile_count == st["misses"] == 1
            assert st["stores"] == 1  # the refused entry is replaced
        finally:
            eng.close()
        warm = start(bank, model, weights)
        try:
            assert warm.compile_count == 0
        finally:
            warm.close()

    def test_fingerprint_mismatch_spoofed_runtime(self, tmp_path,
                                                  monkeypatch):
        # a jaxlib/backend bump changes the runtime tag: every banked
        # entry silently misses (no verify_rejects — the old entries are
        # intact, just keyed away) and the zoo recompiles + repopulates
        model, weights = write_toy(tmp_path)
        bank = tmp_path / "bank"
        start(bank, model, weights).close()
        import caffe_mpi_tpu.utils.compile_cache as cc
        monkeypatch.setattr(cc, "runtime_tag",
                            lambda: "jax-9.9.9/jaxlib-9.9.9/cpu/spoof")
        eng = start(bank, model, weights)
        try:
            st = bank_stats(eng)
            assert eng.compile_count == st["misses"] == eng.warmed_buckets
            assert st["hits"] == 0 and st["verify_rejects"] == 0
            assert st["stores"] == eng.warmed_buckets
        finally:
            eng.close()
        # and the spoofed-runtime entries now warm a same-runtime engine
        eng2 = start(bank, model, weights)
        try:
            assert eng2.compile_count == 0
        finally:
            eng2.close()


# ---------------------------------------------------------------------------
# concurrency: two engines sharing one bank directory


class TestConcurrentWriters:
    def test_two_engines_same_bank(self, tmp_path):
        model, weights = write_toy(tmp_path)
        bank = tmp_path / "bank"
        engines, errors = [], []

        def boot():
            try:
                engines.append(start(bank, model, weights))
            except Exception as e:  # noqa: BLE001 — the test's assertion
                errors.append(e)

        threads = [threading.Thread(target=boot) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        try:
            assert not errors
            for eng in engines:
                st = bank_stats(eng)
                assert eng.compile_count == st["misses"]
                assert eng.compile_count + st["hits"] == eng.warmed_buckets
                assert st["store_failures"] == 0
        finally:
            for eng in engines:
                eng.close()
        # whatever interleaving happened, the committed bank is whole
        warm = start(bank, model, weights)
        try:
            assert warm.compile_count == 0
            assert bank_stats(warm)["hits"] == warm.warmed_buckets
        finally:
            warm.close()


# ---------------------------------------------------------------------------
# fingerprint semantics


class TestFingerprint:
    def _param(self, tmp_path, batch=8):
        model, _ = write_toy(tmp_path, batch=batch)
        return NetParameter.from_file(model)

    def test_stable_and_selective(self, tmp_path):
        p = self._param(tmp_path)
        kw = dict(bucket=4, dtype="f32", out_spec="prob", runtime="rt")
        base = fingerprint(p, **kw)
        assert base == fingerprint(p, **kw)  # deterministic
        assert base != fingerprint(p, **{**kw, "bucket": 8})
        assert base != fingerprint(p, **{**kw, "dtype": "bf16"})
        assert base != fingerprint(p, **{**kw, "out_spec": "env"})
        assert base != fingerprint(p, **{**kw, "runtime": "rt2"})

    def test_topology_in_weights_out(self, tmp_path):
        # the declared batch is normalized away per bucket by the warm
        # path's rewrite, but a topology edit (layer width) must re-key
        pa = self._param(tmp_path)
        pb = self._param(tmp_path)
        kw = dict(bucket=4, dtype="f32", out_spec="prob", runtime="rt")
        assert fingerprint(pa, **kw) == fingerprint(pb, **kw)
        pb.layer[1].inner_product_param.num_output = 6
        assert fingerprint(pa, **kw) != fingerprint(pb, **kw)


# ---------------------------------------------------------------------------
# standalone-artifact manifests (the PR 3 scheme, single-file form)


class TestFileManifest:
    def test_roundtrip_and_commit_record(self, tmp_path):
        p = str(tmp_path / "artifact.bin")
        with open(p, "wb") as f:
            f.write(b"payload bytes")
        mpath = write_file_manifest(p, fingerprint="abc")
        assert os.path.exists(mpath)
        doc = verify_file_manifest(p)
        assert doc is not None and doc["fingerprint"] == "abc"

    def test_missing_manifest_or_file(self, tmp_path):
        p = str(tmp_path / "artifact.bin")
        with open(p, "wb") as f:
            f.write(b"x")
        assert verify_file_manifest(p) is None  # no commit record
        write_file_manifest(p)
        os.remove(p)
        assert verify_file_manifest(p) is None  # record without artifact

    def test_size_and_crc_mismatch(self, tmp_path):
        p = str(tmp_path / "artifact.bin")
        with open(p, "wb") as f:
            f.write(b"payload")
        write_file_manifest(p)
        with open(p, "r+b") as f:
            f.write(b"PAYLOAD")  # same size, different bytes
        assert verify_file_manifest(p) is None
        with open(p, "ab") as f:
            f.write(b"tail")
        assert verify_file_manifest(p) is None


# ---------------------------------------------------------------------------
# bank internals


class TestProgramBank:
    def test_load_absent_counts_plain_miss(self, tmp_path):
        bank = ProgramBank(str(tmp_path / "bank"), BankStats())
        assert bank.load("0" * 32) is None
        st = bank.stats.snapshot()
        assert st["misses"] == 1 and st["verify_rejects"] == 0

    def test_store_unserializable_counts_failure(self, tmp_path):
        bank = ProgramBank(str(tmp_path / "bank"), BankStats())
        assert bank.store("0" * 32, object()) is False
        st = bank.stats.snapshot()
        assert st["store_failures"] == 1 and st["stores"] == 0
        assert not os.listdir(bank.path)


# ---------------------------------------------------------------------------
# the netshape plan: static bytes, admission, and telemetry surface


class TestPlan:
    def test_plan_matches_built_model(self, tmp_path):
        model, weights = write_toy(tmp_path)
        plan = plan_model(NetParameter.from_file(model))
        eng = start(None, model, weights)
        try:
            m = eng.model("m")
            assert tuple(plan["ladder"]) == tuple(m.fwd.ladder)
            assert plan["param_bytes_exact"]
            assert plan["param_bytes"] == m.param_bytes
            assert plan["peak_activation_bytes"] > 0
            # the surfaced plan in stats matches the standalone one
            surfaced = bank_stats(eng)["plan"]["models"]["m"]
            assert surfaced["param_bytes"] == plan["param_bytes"]
            assert surfaced["load_ms"] > 0
        finally:
            eng.close()

    def test_admission_plan_predicts_lru_spill(self, tmp_path):
        model, weights = write_toy(tmp_path)
        pb = plan_model(NetParameter.from_file(model))["param_bytes"]
        # budget fits one model, not two: the planner must predict the
        # load-order LRU spill the engine then actually performs
        budget_mb = pb * 1.5 / 2**20
        planned = plan_admission([("a", pb), ("b", pb)],
                                 int(budget_mb * 2**20))
        assert planned["planned_spills"] == ["a"]
        assert planned["resident"] == ["b"]
        assert not planned["over_budget"]
        eng = ServingEngine(window_ms=0, hbm_mb=budget_mb)
        try:
            eng.load_model("a", model, weights)
            eng.load_model("b", model, weights)
            assert eng.spills == len(planned["planned_spills"])
            adm = bank_stats(eng)["plan"]["admission"]
            assert adm["planned_spills"] == ["a"]
        finally:
            eng.close()

    def test_admission_over_budget_flag(self):
        planned = plan_admission([("a", 100)], 50)
        assert planned["over_budget"]
        assert planned["resident"] == ["a"]  # newest always resident

    def test_plan_bf16_halves_activation_bytes(self, tmp_path):
        model, _ = write_toy(tmp_path)
        p = NetParameter.from_file(model)
        f32 = plan_model(p, dtype="f32")
        bf16 = plan_model(p, dtype="bf16")
        assert bf16["peak_activation_bytes"] == \
            f32["peak_activation_bytes"] // 2


# ---------------------------------------------------------------------------
# the registered fault site exists (doc-drift holds the description)


def test_bank_corrupt_site_registered():
    assert "bank_corrupt" in resilience.FAULT_SITES
