"""Verified hot-swap watcher — the train->serve loop (ISSUE 12).

Reference: the reference framework has no online model-update story at
all — a retrained net reaches its deployment surface by *restarting*
the service with new weights (examples/web_demo/app.py parses
--pretrained_model once at startup; tools/extract_features.cpp is a
batch job). This deployment's training side already publishes
verified-atomic snapshots (utils/resilience.py: crc32c manifest written
last = the commit record, solver.cpp:542-604 is the unverified
original), so the serving plane can trust them as a swap feed.

TPU-native design: `SnapshotWatcher` tails a training run's snapshot
prefix (the run journal + manifest directory listing — cheap, no file
reads until a NEW iteration appears) and live-reloads each newly
*verified* snapshot into an already-serving engine:

  1. **verify first** — `resilience.verify_snapshot` re-checks every
     crc32c before any byte reaches the engine; a torn or bit-rotted
     snapshot is journaled + skipped, never served (`swap_corrupt`
     fault site drives the test).
  2. **canary gate** — `ServingEngine.swap_weights` runs the smallest
     ALREADY-COMPILED bucket program with the candidate weights;
     non-finite or shape-mismatched scores reject the swap and the
     previous weights keep serving (`swap_canary_bad` site).
  3. **zero recompiles** — the swap is a host-side weight import + one
     device upload into shape-identical params; the compiled bucket
     ladder is untouched (`compile_count` is asserted unchanged
     across a swap in tests/test_serving_resilience.py; latency under
     live traffic across a swap has not been measured).

Sharded (.orbax) snapshot sets carry no flat `.caffemodel`, so the
watcher logs-and-skips them — the flat formats are the serve feed.
"""

from __future__ import annotations

import json
import logging
import os
import threading

from ..utils import resilience
from ..utils.resilience import FAULTS
from .errors import SwapError

log = logging.getLogger(__name__)


class SnapshotWatcher:
    """Tail `<prefix>`'s verified snapshots and hot-swap them into
    `engine`'s model `name`. `min_iter` skips snapshots at or below it
    (serve-from-iteration-N startup); rejected iterations (corrupt
    bytes, failed canary) are remembered so real bitrot — which never
    heals — cannot re-reject in a loop every poll."""

    def __init__(self, engine, name: str, prefix: str, *,
                 poll_s: float = 2.0, min_iter: int = 0):
        self.engine = engine
        self.name = name
        self.prefix = prefix
        self.poll_s = float(poll_s)
        self._last_iter = int(min_iter)
        self._rejected: set[int] = set()
        self._warned_orbax = False
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # -- lifecycle ------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="serve-snapshot-watch")
        self._thread.start()
        log.info("serving: watching snapshot prefix %r for model %r "
                 "(poll %.1fs)", self.prefix, self.name, self.poll_s)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2 * self.poll_s + 5.0)
            self._thread = None

    def _loop(self) -> None:
        while not self._stop.wait(self.poll_s):
            try:
                self.check_once()
            # lint: ok(typed-failure) — the watcher must survive a
            # failed poll (half-written snapshot dirs); the next poll
            # retries, and a rejected swap is journaled in check_once
            except Exception:  # noqa: BLE001 — the watcher must survive
                log.exception("serving: snapshot watch poll failed "
                              "(continuing)")

    # -- one poll -------------------------------------------------------
    def check_once(self) -> bool:
        """One poll: swap to the NEWEST verified snapshot beyond the
        last swapped iteration (intermediate snapshots are stale the
        moment a newer one commits — no point serving them in order).
        Returns True iff a swap happened."""
        for it, mpath in resilience.iter_snapshot_manifests(self.prefix):
            if it <= self._last_iter:
                return False  # newest-first listing: nothing new
            if it in self._rejected:
                continue  # durable rot: try the next-older candidate
            return self._try_swap(it, mpath)
        return False

    def _try_swap(self, it: int, mpath: str) -> bool:
        # test-only (swap_corrupt): rot the candidate's model file
        # POST-manifest — the verify below must catch it
        weights_guess = self._model_file(mpath)
        if weights_guess:
            FAULTS.corrupt_file("swap_corrupt", weights_guess)
        doc = resilience.verify_snapshot(mpath)
        if doc is None:
            self._rejected.add(it)
            self.engine.note_swap_rejected(
                self.name, f"snapshot iter {it} failed crc verification "
                f"({mpath})", source=f"iter_{it}")
            return False
        if doc.get("kind") == "orbax":
            # sharded sets have no flat .caffemodel to serve from
            self._last_iter = it  # don't re-consider it every poll
            if not self._warned_orbax:
                self._warned_orbax = True
                log.warning("serving: snapshot prefix %r publishes "
                            "sharded (.orbax) sets; the watcher serves "
                            "flat .caffemodel snapshots only — skipping",
                            self.prefix)
            return False
        ent = doc.get("files", {}).get("model")
        if not ent:
            self._rejected.add(it)
            self.engine.note_swap_rejected(
                self.name, f"snapshot iter {it} manifest has no model "
                "entry", source=f"iter_{it}")
            return False
        weights = os.path.join(os.path.dirname(os.path.abspath(mpath)),
                               ent["file"])
        try:
            self.engine.swap_weights(self.name, weights,
                                     source=f"iter_{it}")
        except SwapError:
            # swap_weights already journaled + counted the rejection
            self._rejected.add(it)
            return False
        self._last_iter = it
        return True

    @staticmethod
    def _model_file(mpath: str) -> str | None:
        """The manifest's model-file path WITHOUT verification — only
        the fault-injection site needs it pre-verify."""
        try:
            with open(mpath) as f:
                doc = json.load(f)
            ent = doc["files"]["model"]["file"]
        except (OSError, ValueError, KeyError, TypeError):
            return None
        return os.path.join(os.path.dirname(os.path.abspath(mpath)), ent)
