"""Persistent AOT program bank — zero-compile serving cold starts.

Replaces: the reference deployment story has no compilation artifact at
all — `caffe.cpp:291` (the `test`/`time` tools) and `classification.cpp`
link precompiled cuDNN kernels, so a restarted server pays only weight
I/O. The TPU-native rebuild pays whole-program XLA compilation per
bucket instead (the PAPERS.md 1810.09868 trade: the compiled executable
IS the deliverable), which turns every `ServingEngine` start into
minutes of recompilation at fleet scale. This module makes the compiled
executable the durable artifact of record (ISSUE 17).

Design: after each bucket warm, `jax.experimental.serialize_executable`
payloads (plus their pickled in/out tree defs and the ids of the devices
the program was compiled for) land in an on-disk bank,
one entry per **fingerprint** — sha256 over the normalized deploy
prototxt text, the bucket size, the serve dtype, the program's output
contract, and the runtime tag (jax + jaxlib versions, backend platform,
device kind — `utils/compile_cache.runtime_tag`). Entries publish with
the PR 3 verified-atomic scheme reused from `utils/resilience.py`:
the payload lands via `atomic_output`, then a crc32c + size sidecar
manifest is written LAST as the commit record. A torn, truncated, or
bit-rotten entry — or any deserialization failure — is a COUNTED miss
that falls back to a fresh compile, never a crash; a fingerprint
mismatch (new jaxlib, edited prototxt, different device kind) misses
silently the same way. Weights are program *inputs*, not part of the
fingerprint — which is exactly why `-watch` hot-swaps stay
bank-compatible. An entry is loaded onto the devices it names, not onto
every device the process sees (jax's default, which turns a one-device
bucket program into one that wants a shard per device of a four-chip
host); a process without those devices counts a miss and recompiles.

The engine-level invariant extends PR 7's `compile_count ==
warmed_buckets` to `compile_count == bank_misses` (and `compile_count +
bank_hits == warmed_buckets`): with the bank off every warm is a miss
and the old equality holds unchanged; bank-warm, a whole-zoo load runs
ZERO compiles.
"""

from __future__ import annotations

import copy
import hashlib
import logging
import os
import pickle
import threading

from ..utils import resilience
from ..utils.resilience import FAULTS, atomic_output

log = logging.getLogger("caffe_mpi_tpu.serving.program_bank")

_ENTRY_SUFFIX = ".xpb"  # "XLA program bank" entry

# Serializes same-process writers across ProgramBank instances (two
# engines sharing one bank dir): atomic_output's stale-temp sweep keys
# temp names on pid alone, so two in-process writers to one entry would
# otherwise sweep each other's in-progress temps. Cross-process writers
# have distinct pids — concurrent publishes are last-wins and a
# manifest/payload interleave at worst verifies as a counted miss.
_WRITE_LOCK = threading.Lock()


def fingerprint(net_param, *, bucket: int, dtype: str, out_spec: str,
                runtime: str) -> str:
    """Bank key for one bucket program: normalized topology text +
    bucket + compute dtype + output contract + runtime tag. Everything
    that selects a different XLA program is in; weights are not."""
    from ..proto.upgrade import normalize_net
    text = normalize_net(copy.deepcopy(net_param)).to_prototxt()
    h = hashlib.sha256()
    for part in (text, str(int(bucket)), dtype or "f32", out_spec,
                 runtime):
        h.update(part.encode())
        h.update(b"\x00")
    return h.hexdigest()[:32]


class BankStats:
    """Thread-safe bank counters, shared engine-wide: every compile is
    a `miss` (bank off included — that keeps `compile_count ==
    bank_misses` an unconditional invariant), every deserialized warm a
    `hit`. `verify_rejects` and `deserialize_failures` are subsets of
    misses that found an entry and refused it."""

    def __init__(self):
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.verify_rejects = 0
        self.deserialize_failures = 0
        self.stores = 0
        self.store_failures = 0

    def bump(self, *fields: str) -> None:
        with self._lock:
            for f in fields:
                setattr(self, f, getattr(self, f) + 1)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "verify_rejects": self.verify_rejects,
                "deserialize_failures": self.deserialize_failures,
                "stores": self.stores,
                "store_failures": self.store_failures,
            }


class ProgramBank:
    """One on-disk bank directory of serialized bucket programs.

    `load` returns a ready-to-call loaded executable or None — None
    covers every failure mode (absent entry, failed manifest verify,
    unpicklable payload, deserialize error) and always means "compile
    fresh and try to repopulate". `store` never raises: a backend whose
    executables do not serialize just counts `store_failures` and the
    engine serves bank-less."""

    def __init__(self, path: str, stats: BankStats | None = None):
        self.path = os.path.abspath(path)
        self.stats = stats or BankStats()
        os.makedirs(self.path, exist_ok=True)
        self._runtime: str | None = None
        # fingerprints whose committed entry this process could not use:
        # store() replaces those instead of keeping "the committed one"
        self._refused: set[str] = set()

    def runtime(self) -> str:
        """Memoized runtime tag — first call touches the backend, so
        the bank computes it only once warm work is already imminent."""
        if self._runtime is None:
            from ..utils.compile_cache import runtime_tag
            self._runtime = runtime_tag()
        return self._runtime

    def entry_path(self, fp: str) -> str:
        return os.path.join(self.path, fp + _ENTRY_SUFFIX)

    def load(self, fp: str):
        """Deserialize the banked program for fingerprint `fp`, or None
        (counted). The manifest verify runs FIRST, so a flipped byte
        past the manifest never reaches the deserializer."""
        entry = self.entry_path(fp)
        doc = resilience.verify_file_manifest(entry)
        if doc is None:
            present = os.path.exists(entry) or os.path.exists(
                entry + resilience._MANIFEST_SUFFIX)
            if present:
                self.stats.bump("misses", "verify_rejects")
                log.warning("program bank: entry %s failed verification "
                            "(torn/rotten); recompiling", entry)
            else:
                self.stats.bump("misses")
            return None
        try:
            import jax
            with open(entry, "rb") as f:
                payload, in_tree, out_tree, device_ids = pickle.load(f)
            from jax.experimental import serialize_executable as se
            by_id = {d.id: d for d in jax.devices()}
            loaded = se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids])
        # lint: ok(typed-failure) — any failure = counted miss + fresh
        # compile + repopulate: the bank contract (docs/serving.md)
        except Exception as e:  # noqa: BLE001 — any failure = recompile
            self.stats.bump("misses", "deserialize_failures")
            self._refused.add(fp)
            log.warning("program bank: entry %s verified but failed to "
                        "deserialize (%r); recompiling", entry, e)
            return None
        self.stats.bump("hits")
        return loaded

    def store(self, fp: str, compiled) -> bool:
        """Publish one compiled executable under fingerprint `fp` with
        the verified-atomic recipe: payload via atomic_output, crc32c
        manifest written LAST. Best-effort by contract."""
        entry = self.entry_path(fp)
        try:
            import jax
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = se.serialize(compiled)
            device_ids = sorted({
                d.id for s in jax.tree.leaves(compiled.input_shardings)
                for d in s.device_set})
            blob = pickle.dumps((payload, in_tree, out_tree, device_ids),
                                protocol=pickle.HIGHEST_PROTOCOL)
        # lint: ok(typed-failure) — counted store_failure; serving
        # continues bank-less for this program by contract
        except Exception as e:  # noqa: BLE001 — backend-dependent
            self.stats.bump("store_failures")
            log.warning("program bank: executable for %s does not "
                        "serialize on this backend (%s); serving "
                        "continues bank-less for this program", fp, e)
            return False
        with _WRITE_LOCK:
            if (fp not in self._refused
                    and resilience.verify_file_manifest(entry) is not None):
                # a concurrent warmer already published this program;
                # both serializations are valid — keep the committed one
                return True
            self._refused.discard(fp)
            try:
                with atomic_output(entry) as tmp:
                    with open(tmp, "wb") as f:
                        f.write(blob)
                resilience.write_file_manifest(entry, fingerprint=fp)
            except OSError as e:
                self.stats.bump("store_failures")
                log.warning("program bank: failed to publish %s (%s)",
                            entry, e)
                return False
        # test-only bitrot: flip a byte of the payload AFTER its
        # manifest committed, so the next load's verify must reject it
        FAULTS.corrupt_file("bank_corrupt", entry)
        self.stats.bump("stores")
        return True
