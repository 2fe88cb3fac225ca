"""Fault-tolerant training runtime — the survivability subsystem.

Reference Caffe assumes a reliable local device: its Snapshot() writes
checkpoint files inline with no integrity metadata (solver.cpp:542-604)
and its Solve() loop has no notion of a device that stops answering.
A TPU job's device call can stop returning — a wedged runtime, a
pre-empted or lost host mid-collective — and leave the process hung
inside uninterruptible C++ dispatch, so fault tolerance is a system
property here, not a user script (the TensorFlow design
position, arXiv 1605.08695; availability-dominated multi-node training,
arXiv 1810.11112). Four pieces, composed by solver/cli:

1. **Verified atomic snapshots** — temp-file + `os.replace` publication,
   a crc32c sidecar manifest (`<prefix>_iter_<N>.manifest.json`: per-file
   crc + size, iteration, wall time) written LAST so "manifest exists"
   == "snapshot complete", verification on load, and newest-prior-
   verified fallback on corruption. `gc_snapshots` enforces the
   `snapshot_keep` solver knob while never deleting the newest verified
   snapshot.
2. **Dispatch watchdog** — a monitor thread timestamps every device
   dispatch/harvest section the solver enters; when one exceeds the
   deadline (a C++ hang no Python signal can interrupt) it
   journals the run state to `<prefix>.run.json` and hard-exits with
   EXIT_WATCHDOG, turning an indefinite hang into a bounded, diagnosable
   failure a supervisor can act on.
3. **Supervised auto-resume** — `supervise()` runs the training child
   under utils/subproc.run_contained with exponential backoff and a
   crash-loop guard; restarts resume from the newest verified snapshot
   (`--resume auto` reads the run manifest + verified-manifest scan).
4. **Fault-injection plane** — env-keyed (`CAFFE_TPU_FAULTS`), zero cost
   when off: one falsy-dict check per site. Drives
   tests/test_fault_tolerance.py (feeder read errors, snapshot
   corruption/truncation, kill-mid-write, simulated dispatch stalls).
"""

from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time
from contextlib import contextmanager

from glob import escape as glob_escape

log = logging.getLogger("caffe_mpi_tpu.resilience")

# distinct exit codes so the supervisor (and the operator's ps/log
# archaeology) can tell a watchdog trip from an injected fault from an
# ordinary crash — and, since ISSUE 4, from a numeric divergence the
# supervisor should REWIND (not merely restart) from
EXIT_WATCHDOG = 86
EXIT_FAULT = 87
EXIT_NUMERIC = 88
# ISSUE 11: cluster losses (a dead peer host, a severed DCN link, a
# coordinator that never answers) share code 87 with injected faults —
# both are environmental failures the supervisor restarts from (not
# rewinds like 88, not dispatch hangs like 86); the run journal's
# `reason` field carries the specific cluster event.
EXIT_CLUSTER = EXIT_FAULT


class ClusterError(RuntimeError):
    """Multi-host cluster formation or liveness failed in a BOUNDED way:
    `init_distributed` exhausted its retry budget against a missing
    coordinator, or a cluster barrier / KV exchange timed out. The CLI
    journals the event to `<prefix>.run.json` and converts this to exit
    code EXIT_CLUSTER (87) so the supervisor restarts the local worker
    instead of the process hanging inside an uninterruptible
    collective.

    `journal_reason` is the run-manifest `reason` the CLI writes for
    the event; raisers override it per instance when the 87 is not a
    loss — the degraded-mode rejoin trigger (ISSUE 19) sets
    "cluster_rejoin" so the supervisor's membership round grows the
    cluster back instead of merely restarting it."""

    journal_reason = "cluster_lost"


class NumericAnomalyError(RuntimeError):
    """Training declared numeric divergence: `guard_max_skips`
    consecutive steps were skipped by the on-device non-finite /
    loss-spike guard. The solver journals the anomaly to
    `<prefix>.run.json` before raising; the CLI converts this to exit
    code EXIT_NUMERIC (88), which the supervisor maps through the
    `anomaly_action` policy (rewind | rewind_lr | abort)."""

    def __init__(self, it: int, consec: int, skipped: int, last_bad: int):
        self.iter = it
        self.consec = consec
        self.skipped = skipped
        self.last_bad = last_bad
        super().__init__(
            f"numeric divergence at iteration {it}: {consec} consecutive "
            f"skipped step(s) ({skipped} total; last bad iteration "
            f"{last_bad})")


class RecordIntegrityError(RuntimeError):
    """One dataset record failed integrity verification (crc32c
    mismatch, structural DB corruption, or an undecodable Datum).
    Deterministic — NOT retried like transient I/O; the feeder
    quarantines the record instead."""

    def __init__(self, source: str, index: int, reason: str):
        self.source = source
        self.index = index
        self.reason = reason
        super().__init__(
            f"record {index} of {source or 'dataset'} failed integrity "
            f"check: {reason}")


class DataIntegrityError(RuntimeError):
    """The quarantine ratio bound was exceeded: corruption is
    systematic (dataset-level), not record-level — a hard, named
    failure instead of silently training on substitutes."""

_STATE_SUFFIXES = (".solverstate", ".solverstate.h5")
_MANIFEST_SUFFIX = ".manifest.json"
_MANIFEST_SCHEMA = 1


# ---------------------------------------------------------------------------
# Fault-injection plane (test-only; env-keyed; zero cost when off)
# ---------------------------------------------------------------------------

# Every registered injection site, in one place: the docs
# (docs/robustness.md) and the tier-1 doc-drift test
# (tests/test_doc_drift.py) both read this, so a site added at a call
# site without a registry entry — or documented without existing —
# fails fast instead of rotting.
FAULT_SITES = {
    "feeder_read": "transient dataset read error (Feeder retry budget)",
    "snapshot_kill": "hard-exit mid-snapshot-write (torn checkpoint)",
    "snapshot_corrupt": "flip a byte of the model file post-manifest",
    "snapshot_sync": "force interval snapshots to write blocking",
    "dispatch_stall": "sleep inside a train dispatch (watchdog trip)",
    "train_abort": "hard-exit at an iteration boundary (crash sim)",
    "nan_grad": "poison float feeds with NaN for iterations "
                "[arg, arg+count) — non-finite loss/gradients",
    "loss_spike": "scale float feeds 1e3x for iterations "
                  "[arg, arg+count) — finite loss explosion",
    "record_corrupt": "flip a byte of record values [arg, arg+count) "
                      "after fetch (bitrot the crc check must catch)",
    "record_decode": "truncate record values [arg, arg+count) so the "
                     "Datum parse fails",
    "host_loss": "kill the local worker at a heartbeat boundary "
                 "(beat seq >= arg) — a peer host dying mid-run",
    "coordinator_down": "fail distributed init for the first `count` "
                        "attempts (missing/unreachable coordinator)",
    "snapshot_shard_corrupt": "flip a byte in one orbax shard "
                              "post-manifest (sharded-snapshot bitrot)",
    "serve_dispatch_stall": "sleep inside a serving dispatch (stall "
                            "breaker trip — a device call that hangs)",
    "swap_corrupt": "flip a byte of a hot-swap candidate's model file "
                    "post-manifest (verify must reject the swap)",
    "swap_canary_bad": "poison a hot-swap candidate's loaded weights "
                       "with NaN (canary gate must roll back)",
    "bank_corrupt": "flip a byte of a program-bank entry post-manifest "
                    "(verify must reject it into a counted bank miss)",
    "replica_dead": "kill a serving replica at a heartbeat boundary "
                    "(beat seq >= arg) — a fleet replica dying "
                    "mid-traffic",
    "fleet_swap_canary_bad": "flip a byte of the fleet's staged swap "
                             "candidate pre-canary (the rolling swap "
                             "must reject and roll back)",
    "host_perma_loss": "go dark at supervisor level for `arg` seconds "
                       "after the worker dies — the whole host (worker "
                       "AND supervisor) is gone, so the survivors must "
                       "degrade instead of waiting for a restart-all",
}

class FaultPlane:
    """Injects failures at named sites, configured from the
    `CAFFE_TPU_FAULTS` env var: comma-separated `site:count:skip:arg`
    entries (count defaults 1, skip 0, arg empty). A site `fire()`s on
    the (skip+1)-th .. (skip+count)-th eligible calls, then never again.
    count <= 0 is STICKY: the site fires on every eligible call for the
    rest of this process (e.g. "the dataset is gone", not "one read
    blipped").

    `CAFFE_TPU_FAULTS_DIR`, when set, makes firing durable ACROSS
    process restarts: a site that has fired its full count (or, for
    sticky sites, fired at all) writes `<dir>/<site>.done`, and any
    later process (the supervised restart) loads that site disabled —
    so "crash once, then succeed" scenarios terminate instead of
    crash-looping.

    Call-site helpers (`maybe_raise`, `maybe_stall`, `maybe_exit`,
    `corrupt_file`) keep injection one line in production code. When the
    env var is unset `_sites` is empty and `fire()` is a single falsy
    dict check — the zero-cost-when-off contract."""

    def __init__(self):
        self._sites: dict[str, dict] = {}
        self._dir = ""
        self._lock = threading.Lock()
        # bumped on every (re)configure — consumers that cache derived
        # state (the solver's wrapped feed_fn) key on it so a
        # reconfiguration mid-run invalidates their cache
        self.generation = 0

    def load_env(self) -> None:
        self.configure(os.environ.get("CAFFE_TPU_FAULTS", ""),
                       once_dir=os.environ.get("CAFFE_TPU_FAULTS_DIR", ""))

    def configure(self, spec: str, once_dir: str = "") -> None:
        self._dir = once_dir
        self._sites = {}
        self.generation += 1
        for entry in (spec or "").split(","):
            entry = entry.strip()
            if not entry:
                continue
            parts = entry.split(":")
            site = parts[0]
            count = int(parts[1]) if len(parts) > 1 and parts[1] else 1
            skip = int(parts[2]) if len(parts) > 2 and parts[2] else 0
            arg = parts[3] if len(parts) > 3 else ""
            if self._done_path(site) and os.path.exists(
                    self._done_path(site)):
                log.info("fault site %r already fired in a previous "
                         "process; disabled", site)
                continue
            self._sites[site] = {"count": count, "skip": skip, "arg": arg}

    def _done_path(self, site: str) -> str:
        return os.path.join(self._dir, f"{site}.done") if self._dir else ""

    def fire(self, site: str, key: float | None = None) -> str | None:
        """Returns the site's arg string when this call should fail,
        else None. `key` (e.g. the current iteration) gates sites whose
        arg is a numeric threshold: they fire only once key >= arg."""
        if not self._sites:
            return None
        with self._lock:
            st = self._sites.get(site)
            if st is None:
                return None
            arg = st["arg"]
            if key is not None and arg:
                try:
                    if key < float(arg):
                        return None
                except ValueError:
                    pass  # non-numeric arg: no threshold gating
            if st["skip"] > 0:
                st["skip"] -= 1
                return None
            if st["count"] <= 0:  # sticky: every call, this process only
                if not st.get("fired"):
                    st["fired"] = True
                    self._mark_done(site)
                return arg
            st["count"] -= 1
            if st["count"] <= 0:
                del self._sites[site]
                self._mark_done(site)
            return arg

    def _mark_done(self, site: str) -> None:
        done = self._done_path(site)
        if done:
            try:
                with open(done, "w") as f:
                    f.write(f"{time.time()}\n")
            except OSError:
                pass

    def active(self, site: str) -> bool:
        """Is `site` configured (without consuming a firing)? The
        zero-cost gate for wrappers that would otherwise add per-call
        work even with faults off."""
        return bool(self._sites) and site in self._sites

    def fire_at(self, site: str, key: float, *,
                durable_done: bool = True) -> str | None:
        """Range-keyed firing: fires iff arg <= key < arg + count,
        WITHOUT consuming the count. Unlike fire(), the decision is a
        pure function of `key` (a record/iteration index), so it is
        deterministic under prefetch-thread call reordering and under
        rebuild-on-demand — the property the feed-poisoning and
        record-corruption sites need for iteration-exact replay.
        durable_done=False skips the cross-process done marker
        (simulated bitrot must PERSIST across a supervised restart,
        while a NaN burst must not re-fire after the rewind)."""
        if not self._sites:
            return None
        with self._lock:
            st = self._sites.get(site)
            if st is None:
                return None
            try:
                lo = float(st["arg"] or 0)
            except ValueError:
                return None
            # count <= 0 keeps the plane-wide STICKY contract: every
            # eligible key from `arg` onward (a finite count bounds the
            # range instead of a consumable budget)
            n = st["count"]
            if key < lo or (n > 0 and key >= lo + n):
                return None
            if durable_done and not st.get("fired"):
                st["fired"] = True
                self._mark_done(site)
            return st["arg"]

    def wrap_feeds(self, feed_fn):
        """Wrap a feed_fn with the `nan_grad` / `loss_spike` poisoning
        sites (ISSUE 4): float leaves of the batch for micro-iterations
        [arg, arg+count) are overwritten with NaN (nan_grad) or scaled
        1e3x (loss_spike). Returns `feed_fn` UNCHANGED when neither
        site is configured — the zero-cost-when-off contract (the
        solver caches the wrapper, so identity matters: a fresh wrapper
        per step() would churn the device feed queue)."""
        if not (self.active("nan_grad") or self.active("loss_spike")):
            return feed_fn
        import numpy as np  # deferred: resilience imports at startup

        def poison(feeds, fn):
            out, hit = {}, False
            for k, v in feeds.items():
                # feeds here are host ndarrays from the batch builder
                # (and this path only exists under fault injection)
                arr = np.asarray(v)  # host-sync: ok
                if np.issubdtype(arr.dtype, np.floating):
                    arr = fn(arr.copy())
                    hit = True
                out[k] = arr
            if not hit:
                # uint8 device-transform staging has no float leaf to
                # poison — silent no-op injection would make a test
                # pass vacuously
                log.warning("fault plane: batch has no float leaves to "
                            "poison (device-transform staging? use "
                            "transform_param { use_gpu_transform: "
                            "false } in the test net)")
            return out

        def wrapped(it):
            feeds = feed_fn(it)
            if self.fire_at("nan_grad", it) is not None:
                log.warning("fault plane: NaN-poisoning feeds for "
                            "micro-iteration %d", it)
                feeds = poison(feeds, lambda a: np.full_like(a, np.nan))
            if self.fire_at("loss_spike", it) is not None:
                log.warning("fault plane: 1e3x-scaling feeds for "
                            "micro-iteration %d", it)
                feeds = poison(feeds, lambda a: a * 1e3)
            return feeds

        return wrapped

    def corrupt_bytes(self, site: str, raw: bytes, key: float) -> bytes:
        """Record-level injection on FETCHED bytes (the mmap itself is
        read-only): `record_corrupt` flips one mid-record byte,
        `record_decode` truncates the record. Keyed by record index and
        durable across restarts (real bitrot does not heal on resume),
        so quarantine decisions replay identically."""
        if not self._sites:
            return raw
        if self.fire_at(site, key, durable_done=False) is not None:
            if site == "record_decode":
                return raw[:max(len(raw) // 2, 1)]
            b = bytearray(raw)
            if b:
                b[len(b) // 2] ^= 0xFF
            return bytes(b)
        return raw

    # -- one-line call-site helpers ------------------------------------
    def maybe_raise(self, site: str, exc_type=OSError, msg: str = "",
                    key: float | None = None) -> None:
        arg = self.fire(site, key=key)
        if arg is not None:
            raise exc_type(msg or f"injected fault at site {site!r}")

    def maybe_stall(self, site: str, key: float | None = None) -> None:
        arg = self.fire(site, key=key)
        if arg is not None:
            secs = float(arg or 30.0)
            log.warning("fault plane: stalling %.1fs at site %r", secs, site)
            time.sleep(secs)

    def maybe_exit(self, site: str, key: float | None = None) -> None:
        arg = self.fire(site, key=key)
        if arg is not None:
            log.warning("fault plane: hard exit at site %r", site)
            sys.stderr.flush()
            os._exit(EXIT_FAULT)

    def corrupt_file(self, site: str, path: str) -> None:
        """Flip one mid-file byte (bitrot/torn-write simulation)."""
        if self.fire(site) is None:
            return
        with open(path, "r+b") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            f.seek(max(size // 2, 0))
            b = f.read(1)
            f.seek(max(size // 2, 0))
            f.write(bytes([(b[0] ^ 0xFF) if b else 0xFF]))
        log.warning("fault plane: corrupted %s at site %r", path, site)


FAULTS = FaultPlane()
FAULTS.load_env()


# ---------------------------------------------------------------------------
# Atomic file publication + crc32c integrity
# ---------------------------------------------------------------------------

@contextmanager
def atomic_output(path: str):
    """Yield a temp path for the caller to write; on clean exit fsync it
    and `os.replace` onto `path` (atomic on POSIX), so readers — and the
    resume scan after a mid-write kill — only ever see absent-or-complete
    files. On error the temp file is removed.

    Stale temps from a previous writer killed mid-write (the pid suffix
    differs) are swept first — writers to one path are serialized
    (wait_snapshots), so anything matching is an orphan."""
    import glob as _glob
    for stale in _glob.glob(f"{glob_escape(path)}.tmp*"):
        try:
            os.unlink(stale)
        except OSError:
            pass
    tmp = f"{path}.tmp{os.getpid()}"
    try:
        yield tmp
        fd = os.open(tmp, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def crc32c_file(path: str, chunk: int = 1 << 22) -> int:
    """Streaming crc32c of a file — hardware-accelerated via
    google_crc32c when installed, else the repo's slice-by-8 table path
    (data/leveldb_io.py)."""
    try:
        from google_crc32c import extend as _extend
    except ImportError:
        _extend = None
    if _extend is None:
        from ..data.leveldb_io import crc32c
        with open(path, "rb") as f:
            return crc32c(f.read())
    crc = 0
    with open(path, "rb") as f:
        while True:
            buf = f.read(chunk)
            if not buf:
                return crc
            crc = _extend(crc, buf)


# ---------------------------------------------------------------------------
# Single-artifact manifests (program-bank entries, ISSUE 17)
# ---------------------------------------------------------------------------

def write_file_manifest(path: str, **meta) -> str:
    """Publish the crc32c commit record for ONE standalone artifact —
    the snapshot-manifest scheme (write_snapshot_manifest) specialised
    to a single file with no iteration counter. Written LAST, after the
    artifact itself landed via atomic_output, so "manifest exists and
    verifies" is the artifact's commit point; extra keyword fields
    (e.g. a program-bank fingerprint) are stored alongside for
    observability."""
    mpath = path + _MANIFEST_SUFFIX
    doc = {"schema": _MANIFEST_SCHEMA, "time": time.time(),
           "files": {"artifact": {
               "file": os.path.basename(path),
               "size": os.path.getsize(path),
               "crc32c": f"{crc32c_file(path):08x}",
           }}}
    doc.update(meta)
    with atomic_output(mpath) as tmp:
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
    return mpath


def verify_file_manifest(path: str) -> dict | None:
    """Re-check a single-artifact manifest (write_file_manifest) against
    the file's current size and crc32c. Returns the manifest dict on
    success, None on ANY failure — missing/unreadable/torn manifest,
    missing artifact, size or crc mismatch — so callers treat None as
    'regenerate the artifact', never as an error to raise."""
    mpath = path + _MANIFEST_SUFFIX
    try:
        with open(mpath) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    ent = (doc.get("files") or {}).get("artifact")
    if not isinstance(ent, dict) or ent.get("file") != os.path.basename(path):
        return None
    try:
        if os.path.getsize(path) != ent["size"]:
            return None
        if f"{crc32c_file(path):08x}" != ent["crc32c"]:
            return None
    except (OSError, TypeError):
        return None
    return doc


# ---------------------------------------------------------------------------
# Snapshot manifests: write / verify / scan / GC
# ---------------------------------------------------------------------------

class SnapshotCorruptError(RuntimeError):
    """A snapshot file failed its manifest crc32c check."""


def manifest_for_state(state_path: str) -> str | None:
    """Sidecar manifest path for a .solverstate[.h5] or a sharded
    .orbax checkpoint directory (ISSUE 11); None for formats without a
    manifest scheme (.npz pre-interop). The orbax manifest KEEPS the
    .orbax infix (`s_iter_N.orbax.manifest.json`) — stripping it would
    collide with a flat snapshot's manifest at the same iteration
    under the same prefix and silently orphan one of the two sets."""
    state_path = state_path.rstrip("/")
    if state_path.endswith(".orbax"):
        return state_path + _MANIFEST_SUFFIX
    for suf in _STATE_SUFFIXES:
        if state_path.endswith(suf):
            return state_path[: -len(suf)] + _MANIFEST_SUFFIX
    return None


def write_snapshot_manifest(state_path: str, it: int,
                            files: dict[str, str]) -> str:
    """Publish the integrity manifest for one snapshot — written LAST
    (after every file it covers), atomically, so its existence is the
    commit point of the whole snapshot. `files` maps role (model/state)
    to path; stored as basenames relative to the manifest's directory."""
    mpath = manifest_for_state(state_path)
    if mpath is None:
        raise ValueError(f"no manifest scheme for {state_path!r}")
    entries = {}
    for role, path in files.items():
        entries[role] = {
            "file": os.path.basename(path),
            "size": os.path.getsize(path),
            "crc32c": f"{crc32c_file(path):08x}",
        }
    doc = {"schema": _MANIFEST_SCHEMA, "iteration": int(it),
           "time": time.time(), "files": entries}
    with atomic_output(mpath) as tmp:
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
    return mpath


def sharded_snapshot_files(orbax_dir: str) -> list[str]:
    """Every regular file under a sharded (.orbax) checkpoint dir,
    sorted by descending size then path — index 0 is the natural
    victim for the `snapshot_shard_corrupt` injection site (the
    biggest file is a tensorstore data shard, not metadata)."""
    out = []
    for root, _dirs, names in os.walk(orbax_dir):
        for name in names:
            out.append(os.path.join(root, name))
    out.sort(key=lambda p: (-os.path.getsize(p), p))
    return out


def write_sharded_manifest(orbax_dir: str, it: int) -> str:
    """Commit record for a sharded (.orbax) snapshot (ISSUE 11): one
    crc32c + size entry PER SHARD FILE under the checkpoint directory,
    written LAST (after the collective orbax save, after the all-hosts
    write barrier, by rank 0 alone) — so "manifest exists" == "every
    host's shards landed". Entries are paths relative to the dir, so
    verify re-walks exactly the recorded shard set and a torn or
    bit-rotted shard set fails as a unit."""
    orbax_dir = os.path.abspath(orbax_dir.rstrip("/"))
    mpath = manifest_for_state(orbax_dir)
    if mpath is None:
        raise ValueError(f"no manifest scheme for {orbax_dir!r}")
    entries = {}
    for path in sharded_snapshot_files(orbax_dir):
        rel = os.path.relpath(path, orbax_dir)
        entries[rel] = {
            "file": rel,
            "size": os.path.getsize(path),
            "crc32c": f"{crc32c_file(path):08x}",
        }
    if not entries:
        raise ValueError(f"sharded snapshot {orbax_dir!r} is empty")
    doc = {"schema": _MANIFEST_SCHEMA, "kind": "orbax",
           "iteration": int(it), "time": time.time(),
           "dir": os.path.basename(orbax_dir), "files": entries}
    with atomic_output(mpath) as tmp:
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
    return mpath


def verify_snapshot(manifest_path: str) -> dict | None:
    """Re-check every file the manifest covers against its recorded size
    and crc32c. Returns the manifest dict (with a resolved 'state' path)
    on success, None on any mismatch / missing file / unreadable
    manifest — callers treat None as 'fall back to an older snapshot'.
    Sharded manifests (kind 'orbax', ISSUE 11) verify every recorded
    shard file relative to the checkpoint dir; 'state' resolves to the
    dir itself."""
    try:
        with open(manifest_path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    base = os.path.dirname(os.path.abspath(manifest_path))
    if doc.get("kind") == "orbax":
        root = os.path.join(base, doc.get("dir") or "")
        if not doc.get("dir") or not os.path.isdir(root) \
                or not doc.get("files"):
            return None
        for ent in doc["files"].values():
            path = os.path.join(root, ent["file"])
            try:
                if os.path.getsize(path) != ent["size"]:
                    return None
                if f"{crc32c_file(path):08x}" != ent["crc32c"]:
                    return None
            except OSError:
                return None
        doc["state"] = root
        doc["manifest"] = os.path.abspath(manifest_path)
        return doc
    state_path = None
    for role, ent in doc.get("files", {}).items():
        path = os.path.join(base, ent["file"])
        try:
            if os.path.getsize(path) != ent["size"]:
                return None
            if f"{crc32c_file(path):08x}" != ent["crc32c"]:
                return None
        except OSError:
            return None
        if role == "state":
            state_path = path
    if state_path is None:
        return None
    doc["state"] = state_path
    doc["manifest"] = os.path.abspath(manifest_path)
    return doc


def iter_snapshot_manifests(prefix: str) -> list[tuple[int, str]]:
    """All `<prefix>_iter_<N>[.orbax].manifest.json` sidecars, newest
    iteration first. Pure directory listing — no file reads, no
    verification."""
    d = os.path.dirname(prefix) or "."
    stem = os.path.basename(prefix) + "_iter_"
    out = []
    try:
        names = os.listdir(d)
    except OSError:
        return []
    for name in names:
        if not (name.startswith(stem) and name.endswith(_MANIFEST_SUFFIX)):
            continue
        mid = name[len(stem):-len(_MANIFEST_SUFFIX)]
        if mid.endswith(".orbax"):  # sharded sets (ISSUE 11)
            mid = mid[: -len(".orbax")]
        if mid.isdigit():
            out.append((int(mid), os.path.join(d, name)))
    out.sort(key=lambda p: p[0], reverse=True)
    return out


def latest_verified_snapshot(prefix: str,
                             max_iter: int | None = None) -> dict | None:
    """Newest snapshot (optionally strictly below `max_iter`) whose
    manifest verifies; corrupt/incomplete candidates are logged and
    skipped — the corruption-fallback half of the resume contract."""
    for it, mpath in iter_snapshot_manifests(prefix):
        if max_iter is not None and it >= max_iter:
            continue
        doc = verify_snapshot(mpath)
        if doc is not None:
            return doc
        log.warning("snapshot manifest %s failed verification "
                    "(corrupt or incomplete); trying an older snapshot",
                    mpath)
    return None


def gc_snapshots(prefix: str, keep: int,
                 assume_verified: str | None = None) -> list[str]:
    """Delete snapshot file sets beyond the newest `keep` manifests,
    never deleting the newest VERIFIED snapshot (if the newest `keep`
    are all corrupt, the last-known-good survives the sweep so resume
    always has somewhere to land). `assume_verified` names a manifest
    the caller KNOWS is good (the one its own writer just published) so
    the scan skips re-reading hundreds of MB it checksummed moments
    ago. Returns removed paths."""
    if keep <= 0:
        return []
    manifests = iter_snapshot_manifests(prefix)
    if len(manifests) <= keep:
        return []
    assumed = os.path.abspath(assume_verified) if assume_verified else None
    newest_verified = None
    for _it, mpath in manifests:  # newest first; stop at the first good
        if os.path.abspath(mpath) == assumed \
                or verify_snapshot(mpath) is not None:
            newest_verified = mpath
            break
    removed = []
    base = os.path.dirname(prefix) or "."
    for _it, mpath in manifests[keep:]:
        if mpath == newest_verified:
            continue
        victims, dirs = [], []
        try:
            with open(mpath) as f:
                doc = json.load(f)
            if doc.get("kind") == "orbax":
                # sharded snapshot (ISSUE 11): the whole checkpoint
                # DIRECTORY is the file set — per-entry unlinks would
                # leave a half-deleted dir that still looks like a
                # checkpoint to a directory listing
                if doc.get("dir"):
                    dirs = [os.path.join(base, doc["dir"])]
            else:
                victims = [os.path.join(base, ent["file"])
                           for ent in doc.get("files", {}).values()]
        except (OSError, ValueError):
            victims = []
        for d in dirs:  # dir first: a crash here leaves the manifest,
            import shutil  # whose verify then fails (never a dir that
            try:           # a later legacy scan could resurrect)
                shutil.rmtree(d)
                removed.append(d)
            except OSError:
                pass
        for path in victims + [mpath]:
            try:
                os.unlink(path)
                removed.append(path)
            except OSError:
                pass
    if removed:
        log.info("snapshot GC (keep=%d): removed %d file(s)", keep,
                 len(removed))
    return removed


# ---------------------------------------------------------------------------
# Run manifest — the journal the watchdog and supervisor share
# ---------------------------------------------------------------------------

def run_manifest_path(prefix: str) -> str:
    return prefix + ".run.json"


# the run manifest has CONCURRENT same-process writers — the async
# snapshot-writer thread journals "snapshot" while the watchdog monitor
# may journal a trip — and atomic_output's temp path is only pid-unique,
# so unserialized writers would sweep each other's in-progress temp
_RUN_MANIFEST_LOCK = threading.Lock()


def write_run_manifest(prefix: str, **fields) -> str:
    """Journal the run state (iteration, last verified snapshot, RNG
    cursor, reason) next to the snapshots. Atomic: a crash mid-journal
    leaves the previous journal intact. Called at every successful
    snapshot and by the watchdog just before a hard exit (the lock
    serializes those two threads)."""
    path = run_manifest_path(prefix)
    doc = {"schema": _MANIFEST_SCHEMA, "time": time.time(),
           "pid": os.getpid(), **fields}
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with _RUN_MANIFEST_LOCK:
        with atomic_output(path) as tmp:
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
    return path


def read_run_manifest(prefix: str) -> dict | None:
    try:
        with open(run_manifest_path(prefix)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


# ---------------------------------------------------------------------------
# Quarantine journal — the data-integrity plane's audit artifact
# ---------------------------------------------------------------------------

class QuarantineLog:
    """Journals quarantined dataset records to `<prefix>.quarantine.json`
    (ISSUE 4). The feeder substitutes a corrupt record deterministically
    (a pure function of the record index), so the journal is an AUDIT
    record, not state resume depends on — but the operator reads it to
    learn WHICH records are rotting, and the replay-determinism test
    asserts two runs produce identical entries.

    Writes are quarantine-rate (one atomic rewrite per newly-bad
    record), never per-iteration. Unconfigured (no path), entries
    accumulate in memory and only log — unit tests and library callers
    pay no filesystem cost."""

    def __init__(self):
        self.path: str | None = None
        self.entries: list[dict] = []
        self._seen: set[tuple] = set()       # journal dedup (incl. preload)
        self._warned: set[tuple] = set()     # THIS process's warnings
        self._lock = threading.Lock()
        self._last_flush = 0.0
        self._dirty = False

    def configure(self, path: str | None) -> None:
        """Bind the journal file (the CLI passes
        `<snapshot_prefix>.quarantine.json`). Existing entries from a
        previous attempt are loaded so a supervised restart appends to
        one continuous record instead of clobbering it."""
        with self._lock:
            self.path = path
            self.entries = []
            self._seen = set()
            self._warned = set()
            if not path:
                return
            try:
                with open(path) as f:
                    doc = json.load(f)
                self.entries = list(doc.get("records", []))
                self._seen = {(e.get("source"), e.get("index"))
                              for e in self.entries}
            except (OSError, ValueError):
                pass

    def record(self, source: str, index: int, substitute: int,
               reason: str, key: str = "") -> None:
        with self._lock:
            if (source, index) in self._seen:
                # already journaled (this process or a previous
                # attempt's preload). A probe-casualty placeholder
                # (substitute -1, "skipped during probing") upgrades in
                # place when the record is later substituted as a
                # primary — the audit must reflect the decision
                # actually replayed every epoch.
                upgraded = False
                if substitute >= 0:
                    for ent in self.entries:
                        if (ent.get("source"), ent.get("index")) == \
                                (source, index) \
                                and ent.get("substitute", -1) < 0:
                            ent["substitute"] = int(substitute)
                            ent["reason"] = reason
                            upgraded = True
                            break
                # the OPERATOR of this process must still hear about it
                # once, or corruption that persists across a dataset
                # "fix" goes silent
                if (source, index) not in self._warned:
                    self._warned.add((source, index))
                    log.warning(
                        "quarantined record %d of %s (-> substitute %d; "
                        "already journaled by a previous attempt): %s",
                        index, source or "dataset", substitute, reason)
                if upgraded:
                    self._flush_locked()
                return
            self._seen.add((source, index))
            self._warned.add((source, index))
            self.entries.append({
                "source": source, "index": int(index), "key": key,
                "substitute": int(substitute), "reason": reason,
                "time": time.time()})
            log.warning("quarantined record %d of %s (-> substitute %d): "
                        "%s", index, source or "dataset", substitute,
                        reason)
            self._flush_locked()

    def _flush_locked(self) -> None:
        """Rewrite the journal (caller holds the lock). Debounced past
        64 entries — one atomic rewrite per second instead of per
        record — so mass corruption near the 5% quarantine bound costs
        O(n) I/O, not O(n^2); the journal is a best-effort audit (the
        substitution itself is replay-deterministic), so a crash losing
        the last debounce window is acceptable."""
        if not self.path:
            return
        self._dirty = True
        now = time.monotonic()
        if len(self.entries) > 64 and now - self._last_flush < 1.0:
            return  # debounced; flush() drains the tail at shutdown
        self._last_flush = now
        self._dirty = False
        doc = {"schema": _MANIFEST_SCHEMA, "records": self.entries}
        try:
            # the first quarantine can precede the first snapshot —
            # the prefix directory may not exist yet
            os.makedirs(os.path.dirname(self.path) or ".",
                        exist_ok=True)
            with atomic_output(self.path) as tmp:
                with open(tmp, "w") as f:
                    json.dump(doc, f, indent=1, sort_keys=True)
        except OSError:
            log.exception("quarantine journal write failed "
                          "(continuing)")

    def flush(self) -> None:
        """Drain any debounced tail — call at clean shutdown (the CLI's
        train teardown does) so the audit is complete even when the
        last quarantines landed inside the debounce window."""
        with self._lock:
            if self._dirty:
                self._last_flush = 0.0  # force the write
                self._flush_locked()

    def count(self) -> int:
        with self._lock:
            return len(self.entries)


QUARANTINE = QuarantineLog()


def quarantine_journal_path(prefix: str, rank: int = 0,
                            world: int = 1,
                            host: int | None = None) -> str:
    """Journal file for one host's quarantine decisions. Single-host
    keeps the classic `<prefix>.quarantine.json`; in a multi-host run
    (ISSUE 11) every host journals its OWN stripe's quarantines to
    `<prefix>.quarantine.r<k>.json` (concurrent atomic rewrites of one
    shared file from N hosts would drop entries), and rank 0 merges the
    per-host journals into the classic path at snapshot time.

    `host` (ISSUE 19) is a STABLE host identity for degraded-mode
    runs: generation remaps reassign ranks, so a rank-keyed journal
    would merge one host's quarantines into another host's audit trail
    after a reshape — when the supervisor publishes an original host
    id (CAFFE_TPU_CLUSTER_SELF), the journal keys on it instead
    (`<prefix>.quarantine.h<host>.json`), surviving every generation.
    Rank-keyed runs (min_hosts unset) keep the classic .r<k> path
    byte-identical."""
    if host is not None and world > 1:
        return prefix + f".quarantine.h{int(host)}.json"
    if world <= 1:
        return prefix + ".quarantine.json"
    return prefix + f".quarantine.r{int(rank)}.json"


def merge_quarantine_journals(prefix: str) -> int:
    """Merge every per-host quarantine journal
    (`<prefix>.quarantine.r*.json`, plus the stable-host-keyed
    `.quarantine.h*.json` spelling degraded-mode runs use — ISSUE 19)
    into the classic `<prefix>.quarantine.json`, deduped by
    (source, index) and sorted for a stable audit. Called by rank 0 at
    snapshot time (the same cadence the single-host journal flushes
    at). Returns the merged record count; 0 with no per-host journals
    (single-host runs never pay this)."""
    import glob as _glob
    d = os.path.dirname(prefix) or "."
    base = os.path.basename(prefix) + ".quarantine."
    parts = sorted(
        p for stem in (base + "r", base + "h")
        for p in _glob.glob(
            os.path.join(glob_escape(d), glob_escape(stem) + "*.json")))
    if not parts:
        return 0
    merged: dict[tuple, dict] = {}
    for path in parts:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        for ent in doc.get("records", []):
            merged.setdefault((ent.get("source"), ent.get("index")), ent)
    records = sorted(merged.values(),
                     key=lambda e: (e.get("source") or "",
                                    e.get("index") or 0))
    out = {"schema": _MANIFEST_SCHEMA, "records": records,
           "merged_from": [os.path.basename(p) for p in parts]}
    with atomic_output(prefix + ".quarantine.json") as tmp:
        with open(tmp, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
    return len(records)


# ---------------------------------------------------------------------------
# Dispatch watchdog
# ---------------------------------------------------------------------------

class DispatchWatchdog:
    """Monitor thread that bounds device dispatch/harvest time.

    The solver wraps every device-blocking region in `section(label)`;
    the monitor wakes every `poll` seconds and, when the OLDEST open
    section has been open longer than `deadline`, calls `on_timeout`
    (the solver's run-state journaler) and hard-exits the process with
    EXIT_WATCHDOG. A hung device call sits inside C++ where no Python
    signal can run — but this thread is already in Python, so
    os._exit still works, converting an indefinite hang into a bounded,
    journaled failure the supervisor restarts from.

    `hard_exit=False` (tests) records the trip in `.tripped` and fires
    `.tripped_event` instead of exiting. The deadline must exceed the
    worst jit-compile a dispatch can trigger — compiles happen inside
    dispatch sections and are legitimate multi-second stalls.

    `pulse` (ISSUE 11): an optional callable invoked once per poll tick
    from the monitor thread — the cross-host heartbeat
    (`HostHeartbeat.tick`) rides here, so one thread owns both liveness
    checks (a dead peer mid-collective and a dispatch that never returns
    are the same shape of failure: an uninterruptible C++ wait only a
    Python side-thread can bound). Pulse exceptions are logged, never
    fatal to the monitor; a deadline of `inf` is allowed for
    heartbeat-only arming (sections then never trip)."""

    def __init__(self, deadline: float, on_timeout=None, *,
                 poll: float | None = None, hard_exit: bool = True,
                 pulse=None):
        self.deadline = float(deadline)
        self.on_timeout = on_timeout
        self.pulse = pulse
        self.poll = poll if poll is not None else min(
            max(self.deadline / 4.0, 0.05), 5.0)
        self.hard_exit = hard_exit
        self.tripped: tuple[str, float] | None = None
        self.tripped_event = threading.Event()
        self._lock = threading.Lock()
        self._open: dict[int, tuple[str, float]] = {}
        self._next = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="dispatch-watchdog")
        self._thread.start()

    @contextmanager
    def section(self, label: str):
        with self._lock:
            token = self._next
            self._next += 1
            self._open[token] = (label, time.monotonic())
        try:
            yield
        finally:
            with self._lock:
                self._open.pop(token, None)

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2 * self.poll + 1.0)

    def open_sections(self) -> list[str]:
        """Labels of the currently-open device sections, oldest first —
        the serving breaker's recovery gate asks this to tell a retired
        stall from a still-wedged call (serving/engine.py)."""
        with self._lock:
            entries = sorted(self._open.values(), key=lambda lt: lt[1])
        return [label for label, _t0 in entries]

    def _run(self) -> None:
        while not self._stop.wait(self.poll):
            if self.pulse is not None:
                try:
                    self.pulse()
                # lint: ok(typed-failure) — the watchdog must survive
                # a bad pulse callback; its deadline check below is
                # the load-bearing path and still runs this tick
                except Exception:
                    log.exception("watchdog: pulse callback failed "
                                  "(continuing)")
            now = time.monotonic()
            with self._lock:
                oldest = min(self._open.values(), key=lambda lt: lt[1],
                             default=None)
            if oldest is None:
                continue
            label, t0 = oldest
            elapsed = now - t0
            if elapsed <= self.deadline:
                continue
            # the consequence differs by mode and the operator reads
            # this line: the training watchdog hard-exits 86, the
            # serving breaker (hard_exit=False, ISSUE 12) keeps the
            # process alive and sheds — claiming "exiting" there sends
            # an operator hunting for a death that never happened
            action = (f"journaling run state and hard-exiting "
                      f"{EXIT_WATCHDOG}" if self.hard_exit else
                      "journaling and tripping the breaker (process "
                      "stays up)")
            log.error("watchdog: device %s exceeded %.1fs deadline "
                      "(%.1fs elapsed) — %s", label, self.deadline,
                      elapsed, action)
            try:
                if self.on_timeout is not None:
                    self.on_timeout(label, elapsed)
            # lint: ok(typed-failure) — the trip proceeds regardless:
            # journaling is best-effort at death, exit 86 is the signal
            except Exception:
                log.exception("watchdog: run-state journal failed")
            self.tripped = (label, elapsed)
            self.tripped_event.set()
            if self.hard_exit:
                logging.shutdown()
                os._exit(EXIT_WATCHDOG)
            return


# ---------------------------------------------------------------------------
# Cross-host heartbeat (ISSUE 11) — host-loss detection
# ---------------------------------------------------------------------------

class DirBeatTransport:
    """Heartbeat transport over a shared directory (`CAFFE_TPU_HB_DIR`):
    one atomically-rewritten sequence file per host. The default
    transport is the jax.distributed key-value store
    (parallel/mesh.py:KVBeatTransport); this one exists for unit tests
    and as an operator escape hatch when checkpoint storage is shared
    but the coordination service is suspect. NFS-grade semantics
    suffice: readers only compare monotone sequence numbers.

    The directory OUTLIVES process incarnations (the KV store does
    not — the coordination service is recreated per cluster epoch), so
    every record is stamped with a per-process incarnation token:
    readers fold a token change into a monotone surrogate sequence
    (a restarted publisher's seq-0 still reads as an ADVANCE, never as
    staleness), and a farewell marker only counts for the incarnation
    whose beats are currently being read — a bye left by an earlier
    clean run cannot disable mourning of the next incarnation."""

    def __init__(self, path: str):
        self.path = path
        os.makedirs(path, exist_ok=True)
        self._nonce = f"{os.getpid()}.{int(time.time() * 1e6)}"
        self._token: dict[int, str] = {}  # per-peer current incarnation
        self._base: dict[int, int] = {}   # surrogate offset per token
        self._hi: dict[int, int] = {}     # highest surrogate returned

    def _beat_file(self, host: int) -> str:
        return os.path.join(self.path, f"hb_{int(host)}")

    def publish(self, host: int, seq: int) -> None:
        with atomic_output(self._beat_file(host)) as tmp:
            with open(tmp, "w") as f:
                f.write(f"{self._nonce}:{int(seq)}")

    def _read(self, host: int) -> tuple[str, int] | None:
        try:
            with open(self._beat_file(host)) as f:
                token, _, seq = f.read().strip().rpartition(":")
            return (token, int(seq)) if token else None
        except (OSError, ValueError):
            return None

    def latest_seq(self, host: int) -> int:
        """Newest beat `host` has published as a surrogate sequence
        monotone ACROSS incarnations, -1 when none. Non-blocking (the
        tick cadence is the retry loop). The latest-not-exact contract
        matters: a reader that arms late or stalls must catch up from
        whatever state exists, never wedge on an overwritten beat."""
        rec = self._read(host)
        if rec is None:
            return -1
        token, seq = rec
        if self._token.get(host) != token:
            # a new incarnation restarts at seq 0: offset it past
            # everything the previous one published
            self._base[host] = self._hi.get(host, -1) + 1
            self._token[host] = token
        val = self._base.get(host, 0) + seq
        self._hi[host] = max(self._hi.get(host, -1), val)
        return val

    def farewell(self, host: int) -> None:
        with atomic_output(os.path.join(self.path,
                                        f"bye_{int(host)}")) as tmp:
            with open(tmp, "w") as f:
                f.write(self._nonce)

    def is_bye(self, host: int) -> bool:
        try:
            with open(os.path.join(self.path, f"bye_{int(host)}")) as f:
                bye_token = f.read().strip()
        except OSError:
            return False
        # only the incarnation whose beats we are reading may say
        # goodbye; a stale marker (or one for a peer we never heard)
        # must not suppress mourning
        return bool(bye_token) and bye_token == self._token.get(host)


class HostHeartbeat:
    """Cross-host liveness detection (ISSUE 11) — the multi-host
    spelling of the hung-dispatch problem: a peer host that dies (or a
    severed DCN link) leaves every survivor blocked inside an
    uninterruptible collective. Detection therefore lives on the watchdog's
    monitor thread (`DispatchWatchdog(pulse=hb.tick)`), not in the
    train loop.

    Protocol: every `interval` seconds each host publishes a
    monotonically sequenced beat; each tick also drains peers' beats.
    A peer silent past `deadline` (measured host-locally — no clock
    sync: receipt time, not payload time) is a LOST HOST: the journal
    callback records it to `<prefix>.run.json` and the process
    hard-exits EXIT_CLUSTER (87) so the supervisor performs the
    coordinated restart. A peer that published its `farewell` marker
    (clean end-of-training, ahead of the exit barrier) is excluded
    instead of mourned. First contact gets `grace` (startup skew:
    peers arm after their own jit compiles).

    `host_loss` fault site: fires at a beat boundary (seq >= arg),
    simulating this host dying mid-run for the recovery suite."""

    def __init__(self, transport, host_id: int, n_hosts: int,
                 deadline: float, *, on_lost=None, interval=None,
                 grace: float | None = None, hard_exit: bool = True):
        self.transport = transport
        self.host = int(host_id)
        self.peers = [p for p in range(int(n_hosts)) if p != self.host]
        self.deadline = float(deadline)
        self.interval = float(interval) if interval else min(
            max(self.deadline / 4.0, 0.1), 5.0)
        self.grace = float(grace) if grace is not None else max(
            3.0 * self.deadline, 30.0)
        self.hard_exit = hard_exit
        self.on_lost = on_lost
        self.lost: tuple[int | str, float] | None = None
        self.lost_event = threading.Event()
        now = time.monotonic()
        self._last_pub = 0.0
        self._seq = 0
        self._first = {p: True for p in self.peers}
        self._last_seen = {p: now for p in self.peers}
        self._last_seq = {p: -1 for p in self.peers}
        self._done: set[int] = set()
        self._pub_warned = False

    def beats_seen(self, peer: int) -> int:
        """Beats observed from `peer` so far (telemetry/tests)."""
        return self._last_seq.get(peer, -1) + 1

    def tick(self) -> None:
        """One liveness round: publish when due, drain peers, mourn the
        stale. Called from the watchdog monitor thread every poll."""
        now = time.monotonic()
        if now - self._last_pub >= self.interval:
            self._last_pub = now
            try:
                self.transport.publish(self.host, self._seq)
            # lint: ok(typed-failure) — publish failure == silence; the
            # peers' deadline clocks decide (the typed outcome is their
            # journaled exit 87, not anything this host could raise)
            except Exception as e:
                if not self._pub_warned:
                    self._pub_warned = True
                    log.warning("heartbeat: publish failed (%s); peers "
                                "will see this host as silent", e)
            # test-only: die AT a beat boundary — the peer hosts must
            # detect the silence and exit 87 within their deadline
            FAULTS.maybe_exit("host_loss", key=self._seq)
            self._seq += 1
        for p in self.peers:
            if p in self._done or self.lost is not None:
                continue
            got = False
            try:
                # latest-not-exact: any ADVANCE counts as a beat, so a
                # reader that armed late or stalled catches up from
                # whatever history the transport still holds — it can
                # never wedge on a pruned sequence number
                seq = self.transport.latest_seq(p)
                if seq > self._last_seq[p]:
                    self._last_seq[p] = seq
                    got = True
            # lint: ok(typed-failure) — KV errors == silence; the
            # deadline clock decides and trips typed below
            except Exception:
                pass  # KV errors == silence; the deadline clock decides
            now = time.monotonic()
            if got:
                self._first[p] = False
                self._last_seen[p] = now
                continue
            try:
                if self.transport.is_bye(p):
                    log.info("heartbeat: host %d finished cleanly", p)
                    self._done.add(p)
                    continue
            # lint: ok(typed-failure) — a failed bye-probe == not a
            # clean departure; the deadline clock trips typed below
            except Exception:
                pass
            allowance = self.deadline + (self.grace if self._first[p]
                                         else 0.0)
            if now - self._last_seen[p] > allowance:
                self._trip(p, now - self._last_seen[p])

    def _trip(self, peer: int, elapsed: float) -> None:
        log.error("heartbeat: host %d silent for %.1fs (deadline %.1fs) "
                  "— peer lost; journaling and exiting %d for the "
                  "supervisor's coordinated restart", peer, elapsed,
                  self.deadline, EXIT_CLUSTER)
        self.lost = (peer, elapsed)
        self.lost_event.set()
        try:
            if self.on_lost is not None:
                self.on_lost(peer, elapsed)
        # lint: ok(typed-failure) — the trip proceeds regardless:
        # journaling is best-effort at death, exit 87 is the signal
        except Exception:
            log.exception("heartbeat: host-lost journal failed")
        if self.hard_exit:
            logging.shutdown()
            os._exit(EXIT_CLUSTER)

    def revive(self, peer: int) -> None:
        """Resume monitoring after `peer` was mourned and supervised
        back up (serving fleet, ISSUE 18). Training mourns once and
        hard-exits for a coordinated restart, so `tick()` latches
        `lost` and stops monitoring EVERY peer; a fleet supervisor
        instead respawns the dead replica in place and needs the
        heartbeat back. Clearing the latch re-arms all peers, and the
        respawned incarnation gets a fresh first-contact grace window
        (it beats from seq 0 under a new transport incarnation token —
        the surrogate-sequence fold reads that as an advance, never as
        staleness)."""
        self.lost = None
        self.lost_event.clear()
        self._first[peer] = True
        self._last_seen[peer] = time.monotonic()
        self._done.discard(peer)

    def farewell(self) -> None:
        """Publish the clean-departure marker (call at solver close,
        after the end-of-training barrier): peers stop expecting beats
        instead of tripping on post-training shutdown skew."""
        try:
            self.transport.farewell(self.host)
        # lint: ok(typed-failure) — best-effort: the exit barrier
        # already synchronized, so a lost farewell costs at worst one
        # spurious peer deadline during shutdown skew
        except Exception:
            pass  # best-effort: the exit barrier already synchronized


# ---------------------------------------------------------------------------
# Bounded retry
# ---------------------------------------------------------------------------

def retrying(fn, *, attempts: int = 4, base_delay: float = 0.05,
             max_delay: float = 2.0, exc_types=(OSError,),
             desc: str = ""):
    """Call `fn()` with bounded exponential backoff on transient errors.
    The LAST failure propagates unchanged (bounded, not infinite — a
    truly dead dataset must surface, and the supervisor owns restarts)."""
    delay = base_delay
    for attempt in range(attempts):
        try:
            return fn()
        except exc_types as e:
            if attempt == attempts - 1:
                raise
            log.warning("transient failure%s (attempt %d/%d): %r; "
                        "retrying in %.2fs",
                        f" in {desc}" if desc else "", attempt + 1,
                        attempts, e, delay)
            time.sleep(delay)
            delay = min(delay * 2, max_delay)


# ---------------------------------------------------------------------------
# Supervisor: contained child + exponential backoff + crash-loop guard
# ---------------------------------------------------------------------------

def supervise(first_cmd: list[str], resume_cmd: list[str],
              max_restarts: int, *, failure_log: str,
              env: dict | None = None, cwd: str | None = None,
              deadline: float | None = None,
              backoff_base: float = 1.0, backoff_cap: float = 60.0,
              anomaly_action: str = "rewind",
              anomaly_lr_mult: float = 0.1,
              journal_prefix: str | None = None) -> int:
    """Run a training child to completion, restarting on failure.

    Attempt 0 runs `first_cmd`; every restart runs `resume_cmd` (which
    carries `--resume auto`, so it lands on the newest verified
    snapshot). Children run under utils/subproc.run_contained — own
    process group, killpg'd on every supervisor exit path, so a
    supervisor kill can't orphan a chip-claiming child. After
    `max_restarts` failed restarts the crash-loop guard gives up with
    the per-attempt record preserved in `failure_log`. Returns the last
    child's exit code (0 on success, None->1 on deadline kill).

    Exit code EXIT_NUMERIC (88, ISSUE 4) — the child's on-device guard
    declared numeric divergence — routes through `anomaly_action`:
    `rewind` restarts from the newest verified snapshot like any
    failure; `rewind_lr` additionally appends `-lr_scale` with
    anomaly_lr_mult compounded per numeric restart, so the replay does
    not step straight back into the divergence; `abort` treats the
    divergence as fatal and returns 88 without restarting.

    Fast-fail doomed formation (ISSUE 19): `journal_prefix` names this
    host's run-manifest journal; when EVERY attempt from the start has
    ended in a fresh `cluster_init_failed` journal, the cluster never
    formed once — the coordinator/peer is unreachable, and burning the
    remaining restarts × CAFFE_TPU_INIT_TIMEOUT would only delay the
    same verdict. Two consecutive such failures give up with one clear
    message naming the unreachable endpoint. A run whose FIRST
    formation succeeded (the journal shows any other reason, or none
    fresh) never fast-fails: a mid-run host loss is exactly what the
    coordinated restart exists for."""
    from .subproc import run_contained
    os.makedirs(os.path.dirname(failure_log) or ".", exist_ok=True)
    rc = 1
    numeric_restarts = 0
    never_formed = True
    for attempt in range(max_restarts + 1):
        cmd = first_cmd if attempt == 0 else list(resume_cmd)
        if attempt > 0 and numeric_restarts and anomaly_action == "rewind_lr":
            cmd = cmd + ["-lr_scale",
                         repr(anomaly_lr_mult ** numeric_restarts)]
        log.info("supervisor: attempt %d/%d: %s", attempt + 1,
                 max_restarts + 1, " ".join(cmd))
        t0 = time.time()
        rc, out, err = run_contained(cmd, deadline, cwd=cwd, env=env,
                                     echo=True)
        dt = time.time() - t0
        if rc == 0:
            if attempt > 0:
                log.info("supervisor: recovered after %d restart(s)",
                         attempt)
            return 0
        reason = ("deadline" if rc is None else
                  "watchdog" if rc == EXIT_WATCHDOG else
                  "numeric divergence" if rc == EXIT_NUMERIC else
                  # 87 = injected fault OR cluster loss (ISSUE 11: a
                  # dead peer / failed distributed init journals the
                  # specific event to <prefix>.run.json); both restart
                  "fault/cluster" if rc == EXIT_FAULT else
                  f"exit {rc}")
        with open(failure_log, "a") as f:
            f.write(f"[{time.ctime()}] attempt {attempt + 1}: {reason} "
                    f"after {dt:.1f}s: {' '.join(cmd)}\n")
            tail = (out or "").strip().splitlines()[-20:] \
                + (err or "").strip().splitlines()[-20:]
            for line in tail:
                f.write(f"    {line}\n")
        if rc == EXIT_NUMERIC:
            if anomaly_action == "abort":
                log.error("supervisor: numeric divergence with "
                          "anomaly_action 'abort'; not restarting "
                          "(log: %s)", failure_log)
                return EXIT_NUMERIC
            numeric_restarts += 1
        # fast-fail doomed formation (ISSUE 19): only a FRESH
        # cluster_init_failed journal (written during this attempt)
        # counts — a stale one from a previous run must not condemn a
        # cluster that is actually forming
        init_fail = None
        if journal_prefix and rc == EXIT_FAULT:
            man = read_run_manifest(journal_prefix)
            if (man and man.get("reason") == "cluster_init_failed"
                    and float(man.get("time", 0) or 0) >= t0):  # lint: ok(host-sync) — journal JSON field, host data
                init_fail = man.get("error", "")
        if init_fail is None:
            never_formed = False
        elif never_formed and attempt >= 1:
            log.error(
                "supervisor: cluster formation failed on every attempt "
                "(%d of them) — %s; the peer is unreachable, so the "
                "remaining %d restart(s) would only replay the same "
                "init timeout. Giving up (log: %s)", attempt + 1,
                init_fail or "distributed init failed",
                max_restarts - attempt, failure_log)
            break
        if attempt >= max_restarts:
            log.error("supervisor: crash-loop guard: %d failure(s); "
                      "giving up (log: %s)", attempt + 1, failure_log)
            break
        delay = min(backoff_base * (2 ** attempt), backoff_cap)
        verb = ("rewinding to" if rc == EXIT_NUMERIC
                else "restarting from")
        log.warning("supervisor: child failed (%s); %s the newest "
                    "verified snapshot in %.1fs", reason, verb, delay)
        time.sleep(delay)
    return 1 if rc is None else rc


# ---------------------------------------------------------------------------
# Degraded-mode elasticity (ISSUE 19) — the generation protocol
# ---------------------------------------------------------------------------
# A PERMANENTLY dead host defeats PR 10's restart-all recovery: every
# survivor re-blocks in init_distributed at the old world size until
# --max-restarts exhausts. The generation protocol reshapes the cluster
# around the survivors instead. It lives at SUPERVISOR level on shared
# storage (the same assumption `--resume auto` already makes for
# snapshots): the coordination-service KV store dies with rank 0's
# worker, so the durable channel is a `<prefix>.cluster/` directory —
# DirBeatTransport supervisor liveness beats (keyed on ORIGINAL host
# ids, which survive every rank remap) plus an atomically-published
# generation record. Workers mirror the live record onto the KV store
# at `caffe/cluster_gen` (mesh.publish_generation) for in-band
# observability; the directory stays the source of truth.

_GEN_FILE = "cluster_gen.json"
_GEN_DONE = "done"


def cluster_dir(prefix: str) -> str:
    """The generation-protocol state directory for a run: beside the
    snapshots (shared storage), one per snapshot prefix."""
    return prefix + ".cluster"


def generation_path(cdir: str) -> str:
    return os.path.join(cdir, _GEN_FILE)


def initial_generation(world: int, coordinator: str) -> dict:
    """Generation 1 — the operator's original launch config. Implicit:
    it is what every supervisor assumes when no generation record
    exists, so a min_hosts run with no failures never writes one."""
    return {"generation": 1, "hosts": list(range(int(world))),
            "world": int(world), "world_full": int(world),
            "coordinator": coordinator, "reason": "cluster_formed"}


def read_generation(cdir: str) -> dict | None:
    """The current generation record, or None (= implicit generation
    1). Torn/invalid records read as None — the publisher's
    atomic_output makes that window a crash artifact, and falling back
    to the previous implicit state is always safe (the next membership
    round republishes)."""
    try:
        with open(generation_path(cdir)) as f:
            doc = json.load(f)
        if int(doc.get("generation", 0)) >= 1 and doc.get("hosts"):
            doc["hosts"] = [int(h) for h in doc["hosts"]]
            return doc
    except (OSError, ValueError, TypeError):
        pass
    return None


def write_generation(cdir: str, gen: dict) -> str:
    """Atomically publish a generation record: the per-generation
    history file `gen_<g>.json` first (the durable audit trail the
    degrade smoke asserts on), then the live `cluster_gen.json` as the
    commit record every parked/restarting supervisor polls."""
    os.makedirs(cdir, exist_ok=True)
    g = int(gen["generation"])
    doc = dict(gen, time=time.time())
    for path in (os.path.join(cdir, f"gen_{g}.json"),
                 generation_path(cdir)):
        with atomic_output(path) as tmp:
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
    try:
        # a new generation means the run is live again: a done marker
        # left by an earlier completed run under this prefix must not
        # release the next run's parked rejoiners
        os.unlink(os.path.join(cdir, _GEN_DONE))
    except OSError:
        pass
    return generation_path(cdir)


def observe_live_hosts(cdir: str, world_full: int, self_host: int,
                       window: float, *, min_beats: int = 2) -> list[int]:
    """One membership round: watch the supervisor beat files for
    `window` seconds and return the sorted original host ids seen
    ALIVE. Prime-then-count: a fresh transport reads each host's
    current beat first, then only ADVANCES count — a frozen file left
    by a dead incarnation never reads as liveness, while a revived
    host's new incarnation token folds into a surrogate advance
    (DirBeatTransport). `min_beats` >= 2 rejects a single straggler
    flush from a host that died mid-publish. The observer itself is
    always live."""
    tr = DirBeatTransport(os.path.join(cdir, "hb"))
    hosts = range(int(world_full))
    base = {h: tr.latest_seq(h) for h in hosts}
    advances = {h: 0 for h in hosts}
    t_end = time.monotonic() + max(window, 0.2)
    while time.monotonic() < t_end:
        time.sleep(min(0.1, window / 4))
        for h in hosts:
            seq = tr.latest_seq(h)
            if seq > base[h]:
                advances[h] += seq - base[h]
                base[h] = seq
    live = {h for h in hosts if advances[h] >= min_beats}
    live.add(int(self_host))
    return sorted(live)


class SupervisorBeat:
    """Daemon thread publishing this SUPERVISOR's liveness beats
    (original host id key) to the cluster directory. Distinct from the
    worker's in-band heartbeat (HostHeartbeat): the worker's dies with
    the worker, which is precisely when membership must still be
    observable — a host whose supervisor beats is a rejoin candidate
    even while its worker is down. pause()/resume() exist for the
    `host_perma_loss` fault site (the whole host going dark)."""

    def __init__(self, cdir: str, host_id: int, interval: float):
        self.transport = DirBeatTransport(os.path.join(cdir, "hb"))
        self.host = int(host_id)
        self.interval = max(float(interval), 0.05)
        self._stop = threading.Event()
        self._paused = threading.Event()
        self._seq = 0
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=f"sup-beat-{self.host}")

    def start(self) -> None:
        self._thread.start()

    # lint: ok(thread-crash) — a silent supervisor beat IS the loss
    # signal: peers mourn the silence and the membership round decides
    # (a crashed beat thread and a dead supervisor look identical by
    # design, and both resolve through the same degraded-mode path)
    def _run(self) -> None:
        while not self._stop.is_set():
            if not self._paused.is_set():
                try:
                    self.transport.publish(self.host, self._seq)
                    self._seq += 1
                except OSError as e:
                    log.warning("supervisor beat publish failed: %s", e)
            self._stop.wait(self.interval)

    def pause(self) -> None:
        self._paused.set()

    def resume(self) -> None:
        self._paused.clear()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5.0)


def _wait_generation_advance(cdir: str, beyond: int,
                             timeout: float) -> dict | None:
    """Poll for a generation record newer than `beyond` (the
    non-publisher survivors waiting out the lowest-rank's membership
    round)."""
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        gen = read_generation(cdir)
        if gen and gen["generation"] > beyond:
            return gen
        time.sleep(0.2)
    return None


def _rejoin_wait(cdir: str, host_id: int, beyond: int,
                 park_deadline: float) -> dict | str | None:
    """Park a host excluded from the current generation: keep
    publishing supervisor beats (the SupervisorBeat thread is already
    running) so rank 0's snapshot-boundary rejoin check can see this
    host alive, and poll until a generation re-admits it, the run
    finishes (`done` marker), or the park deadline lapses."""
    log.info("rejoin-wait: generation %d excludes host %d; parking, "
             "publishing beats until rank 0 re-admits this host at a "
             "snapshot boundary", beyond, host_id)
    t_end = time.monotonic() + park_deadline
    while time.monotonic() < t_end:
        if os.path.exists(os.path.join(cdir, _GEN_DONE)):
            return "done"
        gen = read_generation(cdir)
        if gen and gen["generation"] > beyond \
                and int(host_id) in gen["hosts"]:
            return gen
        time.sleep(0.25)
    return None


def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def supervise_elastic(build_cmd, *, prefix: str, host_id: int,
                      world_full: int, min_hosts: int,
                      host_deadline: float, coordinator_host: str,
                      coordinator: str, max_restarts: int,
                      failure_log: str, env: dict | None = None,
                      cwd: str | None = None,
                      deadline: float | None = None,
                      backoff_base: float = 1.0,
                      backoff_cap: float = 60.0,
                      anomaly_action: str = "rewind",
                      anomaly_lr_mult: float = 0.1,
                      park_deadline: float = 900.0) -> int:
    """Degraded-mode supervisor (ISSUE 19): `supervise()` plus the
    generation protocol. `build_cmd(gen, rank, resume)` returns the
    worker argv for one generation — remapped `-hosts W' -host_id k'
    -coordinator <epoch>` with `--resume auto` on restarts.

    Per child failure, in order:
    1. `host_perma_loss` fault site — this supervisor goes dark for
       `arg` seconds (beats paused), simulating the whole host dead,
       then revives into step 2.
    2. A NEWER generation exists: a peer already reshaped the cluster.
       Including this host -> switch to it with a FRESH restart budget
       (a generation switch is recovery, not a crash loop); excluding
       it -> rejoin-wait, parked until rank 0 re-admits it at a
       snapshot boundary (or the run finishes).
    3. Exit 87 (cluster event): run a membership round over the
       supervisor beats for ~`host_deadline`. A changed host set with
       >= min_hosts survivors is published as generation g+1 by the
       LOWEST surviving host (who is the new rank 0, so it allocates
       the new coordinator epoch on its own address); the others wait
       for that record. Journal events `cluster_degraded:<g>` /
       `cluster_regrown:<g>` land in the run manifest and in the
       generation history (`gen_<g>.json`).
    4. Same membership (transient loss) or non-cluster failure: the
       plain supervised restart with exponential backoff, bounded by
       `max_restarts` WITHIN the current generation.

    A clean exit in a reshaped run publishes the `done` marker so
    parked hosts return 0 instead of waiting out their park deadline."""
    from .subproc import run_contained
    os.makedirs(os.path.dirname(failure_log) or ".", exist_ok=True)
    cdir = cluster_dir(prefix)
    os.makedirs(cdir, exist_ok=True)
    interval = min(max(float(host_deadline) / 4.0, 0.1), 2.0)
    beat = SupervisorBeat(cdir, host_id, interval)
    beat.start()
    cur = read_generation(cdir) or initial_generation(world_full,
                                                      coordinator)
    attempt = 0
    resume = cur["generation"] > 1
    numeric_restarts = 0
    rc: int | None = 1
    try:
        while True:
            if int(host_id) not in cur["hosts"]:
                got = _rejoin_wait(cdir, host_id, cur["generation"],
                                   park_deadline)
                if got == "done":
                    log.info("rejoin-wait: run finished without this "
                             "host; exiting clean")
                    return 0
                if got is None:
                    log.error("rejoin-wait: no generation re-admitted "
                              "host %d within %.0fs; giving up",
                              host_id, park_deadline)
                    return 1
                cur, attempt, resume = got, 0, True
                continue
            rank = cur["hosts"].index(int(host_id))
            cmd = list(build_cmd(cur, rank, resume))
            if resume and numeric_restarts \
                    and anomaly_action == "rewind_lr":
                cmd += ["-lr_scale",
                        repr(anomaly_lr_mult ** numeric_restarts)]
            child_env = dict(env if env is not None else os.environ)
            child_env.update(
                CAFFE_SUPERVISED_CHILD="1",
                CAFFE_TPU_CLUSTER_DIR=cdir,
                CAFFE_TPU_CLUSTER_GEN=str(cur["generation"]),
                CAFFE_TPU_CLUSTER_HOSTS=",".join(
                    str(h) for h in cur["hosts"]),
                CAFFE_TPU_CLUSTER_SELF=str(int(host_id)),
                CAFFE_TPU_WORLD_FULL=str(
                    cur.get("world_full", world_full)),
                CAFFE_TPU_CLUSTER_DEADLINE=repr(float(host_deadline)))  # lint: ok(host-sync) — host scalar knob
            log.info("supervisor[gen %d]: attempt %d/%d as rank %d/%d: "
                     "%s", cur["generation"], attempt + 1,
                     max_restarts + 1, rank, cur["world"], " ".join(cmd))
            t0 = time.time()
            rc, out, err = run_contained(cmd, deadline, cwd=cwd,
                                         env=child_env, echo=True)
            dt = time.time() - t0
            if rc == 0:
                if cur["generation"] > 1:
                    # release any parked excluded host. NOT
                    # atomic_output: every finishing supervisor writes
                    # this marker CONCURRENTLY and the stale-tmp sweep
                    # assumes serialized writers; only the marker's
                    # existence signals, so a plain racy write is
                    # exactly right
                    try:
                        with open(os.path.join(cdir, _GEN_DONE),
                                  "w") as f:
                            f.write(f"{time.time()}\n")
                    except OSError as e:
                        log.warning("done-marker write failed "
                                    "(a peer's likely landed): %s", e)
                if attempt > 0 or cur["generation"] > 1:
                    log.info("supervisor: recovered (generation %d, %d "
                             "restart(s) in it)", cur["generation"],
                             attempt)
                return 0
            reason = ("deadline" if rc is None else
                      "watchdog" if rc == EXIT_WATCHDOG else
                      "numeric divergence" if rc == EXIT_NUMERIC else
                      "fault/cluster" if rc == EXIT_FAULT else
                      f"exit {rc}")
            with open(failure_log, "a") as f:
                f.write(f"[{time.ctime()}] gen {cur['generation']} "
                        f"attempt {attempt + 1}: {reason} after "
                        f"{dt:.1f}s: {' '.join(cmd)}\n")
                tail = (out or "").strip().splitlines()[-20:] \
                    + (err or "").strip().splitlines()[-20:]
                for line in tail:
                    f.write(f"    {line}\n")
            if rc == EXIT_NUMERIC:
                if anomaly_action == "abort":
                    log.error("supervisor: numeric divergence with "
                              "anomaly_action 'abort'; not restarting "
                              "(log: %s)", failure_log)
                    return EXIT_NUMERIC
                numeric_restarts += 1
            # test-only: the whole host (supervisor included) goes dark
            # for `arg` seconds — the survivors must degrade around it,
            # and its revival must re-enter via rejoin-wait
            dark = FAULTS.fire("host_perma_loss")
            if dark is not None:
                park = float(dark) if dark else 8.0  # lint: ok(host-sync) — fault-spec string arg
                log.warning("fault host_perma_loss: host %d supervisor "
                            "dark for %.1fs", host_id, park)
                beat.pause()
                time.sleep(park)
                beat.resume()
                log.warning("fault host_perma_loss: host %d supervisor "
                            "revived", host_id)
            newer = read_generation(cdir)
            if newer and newer["generation"] > cur["generation"]:
                log.info("supervisor: generation %d -> %d (published "
                         "by a peer while this host was down)",
                         cur["generation"], newer["generation"])
                cur, attempt, resume = newer, 0, True
                continue
            if rc == EXIT_CLUSTER:
                window = max(float(host_deadline), 8 * interval)  # lint: ok(host-sync) — host scalar knob
                live = observe_live_hosts(cdir, world_full, host_id,
                                          window)
                if sorted(live) != sorted(cur["hosts"]) \
                        and len(live) >= max(int(min_hosts), 1):
                    if min(live) == int(host_id):
                        g = cur["generation"] + 1
                        event = ("cluster_degraded"
                                 if len(live) < len(cur["hosts"])
                                 else "cluster_regrown")
                        # the publisher is the LOWEST survivor == the
                        # new rank 0 == the host the new coordination
                        # service must run on: a fresh port on its own
                        # address is always bindable by its own worker
                        newgen = {
                            "generation": g, "hosts": live,
                            "world": len(live),
                            "world_full": int(world_full),
                            "coordinator":
                                f"{coordinator_host}:{_free_port()}",
                            "reason": event,
                            "prev_hosts": cur["hosts"]}
                        write_generation(cdir, newgen)
                        try:
                            write_run_manifest(
                                prefix, reason=f"{event}:{g}",
                                generation=g, hosts=live,
                                world=len(live),
                                world_full=int(world_full))
                        except OSError:
                            log.exception("generation journal failed "
                                          "(continuing)")
                        log.warning(
                            "supervisor: published generation %d "
                            "(%s): hosts %s -> %s, world %d", g,
                            event, cur["hosts"], live, len(live))
                        cur, attempt, resume = newgen, 0, True
                        continue
                    got = _wait_generation_advance(
                        cdir, cur["generation"], window + 15.0)
                    if got is not None:
                        cur, attempt, resume = got, 0, True
                        continue
                    log.warning("supervisor: membership changed (%s -> "
                                "%s) but host %d never published a "
                                "generation; falling back to a plain "
                                "restart", cur["hosts"], live,
                                min(live))
            attempt += 1
            if attempt > max_restarts:
                log.error("supervisor: crash-loop guard: %d failure(s) "
                          "in generation %d; giving up (log: %s)",
                          attempt, cur["generation"], failure_log)
                return 1 if rc is None else rc
            delay = min(backoff_base * (2 ** (attempt - 1)), backoff_cap)
            verb = ("rewinding to" if rc == EXIT_NUMERIC
                    else "restarting from")
            log.warning("supervisor: child failed (%s); %s the newest "
                        "verified snapshot in %.1fs", reason, verb,
                        delay)
            time.sleep(delay)
            resume = True
    finally:
        beat.stop()
