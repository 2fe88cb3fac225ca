"""Declared lock-nesting order for the threaded planes (ISSUE 13).

Reference: the original stack's concurrency discipline lives in C++
review lore — BasePrefetchingDataLayer's free/full queues
(base_data_layer.hpp:100-159) and DataReader's per-solver queue pairs
(data_reader.hpp:28-53) encode "who may hold what while touching what"
only in reviewers' heads. This repo grew the same lore across the
serving/feeder/resilience review rounds (PRs 7, 11, 12): which lock may
nest inside which was decided in review comments and CHANGES.md, then
re-litigated every time a thread was touched.

This module makes the decisions LAW: `LOCK_ORDER` is the declared
partial order over the tree's lock aliases (serving/engine.py,
serving/batcher.py, data/feeder.py, data/datasets.py, data/decode.py,
data/leveldb_io.py, utils/resilience.py), and the tpulint `lock-order`
pass (tools/lint/concurrency.py, docs/static_analysis.md) checks every
syntactic nesting — direct `with` nesting plus lock acquisitions
reachable through resolvable calls — against it. A nesting pair that is
neither declared here nor waived in the diff fails lint; an INVERTED
pair (the declared order run backwards) fails louder. A pair absent
from this order is therefore forbidden by default — e.g. holding
`ServingEngine._lock` while waiting on an upload lock is the PR 11
swap-vs-spill deadlock shape, and stays undeclarable.

Lock ids are `ClassName.attr` for instance locks and
`module_stem.NAME` for module-level locks, matching what the pass
discovers from `self.X = threading.Lock()/RLock()/Condition()` and
`NAME = threading.Lock()` assignments. The pass also drift-holds this
file: an id naming a lock that no longer exists in the tree is itself
a finding, so the registry cannot outlive the code it governs.
"""

from __future__ import annotations

# Allowed nesting edges, outer -> inner, with the review decision that
# established each. The pass takes the transitive closure, so a->b and
# b->c also permit a->c.
LOCK_ORDER: tuple[tuple[str, str], ...] = (
    # swap_weights commits under the engine lock while holding the
    # model's upload lock (PR 12): a concurrent ensure_resident holding
    # _upload_lock for a seconds-long upload only delays the commit,
    # never the engine lock. The REVERSE (engine._lock held while
    # waiting on an upload lock) is the PR 11 deadlock shape and is
    # deliberately not declared.
    ("InferenceModel._upload_lock", "ServingEngine._lock"),
    # the dispatcher resolves models (engine.model / note_unhealthy_shed
    # -> engine._lock) while holding the batching condition variable;
    # engine methods never touch batcher state under engine._lock, so
    # the nesting is one-directional (PR 7/12 review rounds).
    ("Batcher._cv", "ServingEngine._lock"),
    # probe_recovery respawns dead worker threads (ensure_threads ->
    # batcher._cv) and inspects the tripped watchdog (open_sections ->
    # DispatchWatchdog._lock) while serializing recovery probes.
    ("ServingEngine._probe_lock", "Batcher._cv"),
    ("ServingEngine._probe_lock", "DispatchWatchdog._lock"),
    # recovery journals to the shared run manifest while still holding
    # the probe lock (write_run_manifest serializes its own writers).
    ("ServingEngine._probe_lock", "resilience._RUN_MANIFEST_LOCK"),
    # compile_bucket counts its compile while serializing the warm path.
    ("BucketedForward._lock", "CompileCounter._lock"),
    # the program bank (ISSUE 17) loads/stores entries inside the same
    # warm serialization: bank counters bump under BankStats._lock, and
    # store() serializes same-process writers across engines with the
    # module-level write lock (atomic_output temp names key on pid, so
    # unserialized in-process writers would sweep each other's temps).
    ("BucketedForward._lock", "BankStats._lock"),
    ("BucketedForward._lock", "program_bank._WRITE_LOCK"),
    # store() counts a failed publish while still serializing writers:
    # bump() holds BankStats._lock for six attribute increments and
    # never blocks or takes further locks, so the nesting is one-way.
    ("program_bank._WRITE_LOCK", "BankStats._lock"),
    # the fleet router's rolling swap (ISSUE 18): _swap_lock serializes
    # a rollout end-to-end (stage -> canary -> propagate -> rollback)
    # and nests _lock only for rotation snapshots and counter bumps —
    # every replica HTTP call and file copy runs with _lock RELEASED.
    # The reverse (holding _lock across a swap) would park every
    # routed request behind a multi-second rollout and is undeclared.
    ("FleetRouter._swap_lock", "FleetRouter._lock"),
    # the rollout journals rejections/rollbacks while still serialized
    # (write_run_manifest serializes its own same-process writers).
    ("FleetRouter._swap_lock", "resilience._RUN_MANIFEST_LOCK"),
)

# Cross-object attribute types the AST cannot infer (constructor
# parameters stored as attributes). The lock-order pass uses these to
# resolve `self._engine.model(...)`-style calls to the class whose
# locks they acquire; the pass drift-holds both sides of every entry.
ATTR_TYPES: dict[str, str] = {
    "Batcher._engine": "ServingEngine",
    "BucketedForward.counter": "CompileCounter",
    "BucketedForward._bank": "ProgramBank",
    "BucketedForward._bank_stats": "BankStats",
    "ProgramBank.stats": "BankStats",
    "ServingEngine.bank": "ProgramBank",
    "ServingEngine.bank_stats": "BankStats",
}
