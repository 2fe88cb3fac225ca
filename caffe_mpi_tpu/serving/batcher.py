"""Continuous batching — request queue, batching window, async harvest.

Reference: examples/web_demo/app.py serves one image per HTTP request
through Classifier.predict — every arrival pays a full forward at the
deploy batch, and the host blocks on the device for each one. The
reference framework's own throughput story (tools/extract_features.cpp,
python/caffe/classifier.py) is offline batching; it has no online
batcher.

TPU-native design: arrivals land in a queue; a single dispatcher thread
closes a batch when either the batching window (measured from the
batch's FIRST request) expires or a full max-size bucket is available,
pads it to the smallest ladder bucket (engine.py — every bucket is an
AOT-compiled program, so arrival-size variance never compiles), and
dispatches WITHOUT waiting for the result: jax returns device futures,
and a separate harvest thread materializes them out-of-band. This is
the DeviceFeedQueue recipe from training (data/feeder.py) applied to
serving — the device round trip of batch k overlaps the assembly of batch k+1, so
sustained img/s approaches device throughput instead of
1 / (RTT + compute).
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future, InvalidStateError
from dataclasses import dataclass, field

import numpy as np

from ..utils.resilience import FAULTS
from .errors import (DeadlineError, EngineClosedError,
                     EngineUnhealthyError, ShedError)

log = logging.getLogger(__name__)

_MAX_RECORDS = 10000  # telemetry ring: enough for p99 at serving rates


@dataclass
class _Request:
    model: str
    data: np.ndarray
    t_enqueue: float
    future: Future = field(default_factory=Future)
    # ISSUE 14: True while `data` is a raw DECODED image ((3, h, w) BGR
    # uint8) whose preprocessing is deferred to the window close — the
    # dispatcher materializes the net input row (one fused native call
    # per window) before stacking the batch
    raw: bool = False


class Batcher:
    """One dispatcher thread + one harvest thread around the engine."""

    def __init__(self, engine):
        self._engine = engine
        self._pending: deque[_Request] = deque()
        # per-model pending counts (guarded by _cv): the window wait
        # checks group-readiness on every submit notify, and a deque
        # scan there is O(backlog) per arrival
        self._pending_by_model: dict[str, int] = {}
        self._cv = threading.Condition()
        self._harvest_q: queue.Queue = queue.Queue()
        self._records: deque[dict] = deque(maxlen=_MAX_RECORDS)
        self._rec_lock = threading.Lock()
        # (model, real_images, bucket) per dispatch, in dispatch order —
        # capped like the latency ring (a serve_forever process would
        # otherwise grow it for life); dispatch_count is the all-time total
        self.dispatches: deque[tuple[str, int, int]] = deque(
            maxlen=_MAX_RECORDS)
        self.dispatch_count = 0
        self._outstanding = 0
        self._idle = threading.Event()
        self._idle.set()
        self._stop = False
        self._draining = False
        self._threads: list[threading.Thread] = []
        # resilience telemetry + in-flight registry (ISSUE 12):
        # dispatched-but-unresolved groups, so the stall breaker can
        # fail their futures from the monitor thread while the hung
        # dispatch/harvest thread is stuck inside C++
        self.shed_count = 0
        self.deadline_count = 0
        self.max_queue_depth = 0
        self._inflight: dict[int, list[_Request]] = {}
        self._inflight_next = 0

    # -- lifecycle ------------------------------------------------------
    def start(self) -> None:
        if self._threads:
            return
        for name, target in (("serve-dispatch", self._dispatch_loop),
                             ("serve-harvest", self._harvest_loop)):
            t = threading.Thread(target=target, name=name, daemon=True)
            t.start()
            # lint: ok(thread-shared-mutation) — callers serialize:
            # submit() holds _cv, and the engine constructor runs
            # before any worker thread exists
            self._threads.append(t)

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        # order matters: join the DISPATCHER before the harvest sentinel,
        # so an in-flight dispatch's item is enqueued ahead of None and
        # its futures still resolve (Ctrl-C with a request in flight).
        # 60 s covers the slow legitimate dispatches (spill re-upload,
        # cold-bucket compile); a dispatcher alive past that is wedged
        # in device code — warn and abandon rather than hang close()
        for t in self._threads[:1]:
            t.join(timeout=60)
            if t.is_alive():
                log.warning("serving: dispatcher still busy at close; "
                            "in-flight futures may be abandoned")
        self._harvest_q.put(None)
        for t in self._threads[1:]:
            t.join(timeout=10)
        # lint: ok(thread-shared-mutation) — the workers were joined
        # (or declared wedged and abandoned) just above, and
        # ensure_threads refuses to respawn once _stop is set
        self._threads = []
        # a dispatch that outlived the join enqueues AFTER the sentinel,
        # into a queue nobody reads — fail those futures instead of
        # leaving callers blocked on a PENDING result forever
        while True:
            try:
                item = self._harvest_q.get_nowait()
            except queue.Empty:
                break
            if item is None:
                continue
            self._done_inflight(item[5])
            self._engine.note_retire(item[1])
            for r in item[0]:
                self._resolve(r.future,
                              exc=EngineClosedError("serving engine closed"))
            self._retire(len(item[0]))
        with self._cv:
            while self._pending:
                self._pending.popleft().future.cancel()
                self._outstanding -= 1
            self._pending_by_model.clear()
            if self._outstanding <= 0:
                self._idle.set()  # cancelled requests never harvest

    def shutdown(self, timeout: float = 60.0) -> None:
        """Graceful drain (ISSUE 12): stop accepting new requests, make
        the dispatcher flush its open window immediately, wait for every
        accepted request to resolve, then close. Unlike close(), nothing
        admitted before the drain began is cancelled."""
        with self._cv:
            self._draining = True
            self._cv.notify_all()  # wake the window wait to flush now
        try:
            self.drain(timeout)
        except TimeoutError:
            log.warning("serving: graceful drain timed out after %.0fs; "
                        "cancelling the stragglers", timeout)
        self.close()

    def ensure_threads(self) -> None:
        """Recovery path (ISSUE 12): respawn worker threads that DIED
        (an exception escaped their loop). Threads that are alive —
        even wedged inside a hung device call — are left alone: a
        duplicate dispatcher would double-pop the queue, and a wedged
        call cannot be reclaimed in-process anyway."""
        targets = (("serve-dispatch", self._dispatch_loop),
                   ("serve-harvest", self._harvest_loop))
        with self._cv:
            if self._stop or not self._threads:
                return
            for i, (name, target) in enumerate(targets):
                if i < len(self._threads) and self._threads[i].is_alive():
                    continue
                t = threading.Thread(target=target, name=name, daemon=True)
                t.start()
                self._threads[i] = t
                log.warning("serving: respawned dead %s thread", name)

    # -- submission -----------------------------------------------------
    def submit(self, model: str, data: np.ndarray,
               raw_mode: bool = False) -> Future:
        with self._cv:
            if self._stop or self._draining:
                raise EngineClosedError("serving engine is closed")
            if not self._engine.healthy:
                # re-check under _cv: engine.submit's lock-free health
                # check can race the breaker trip, and a request that
                # lands in _pending AFTER fail_inflight drained it sits
                # behind a wedged dispatcher forever (fail_inflight
                # also holds _cv, so this check closes the race)
                self._engine.note_unhealthy_shed()
                raise EngineUnhealthyError(
                    "serving engine unhealthy (dispatch stall breaker "
                    "open); request shed")
            limit = self._engine.queue_limit
            if limit and len(self._pending) >= limit:
                # load-shedding admission control (ISSUE 12): fail FAST
                # in the caller's thread — an unbounded backlog just
                # converts overload into universal deadline misses
                self.shed_count += 1
                raise ShedError(
                    f"serving backlog at serve_queue_limit={limit}; "
                    "request shed")
            if not self._threads:
                self.start()
            # the request (and its Future) is constructed only AFTER
            # every admission raise above: a shed/closed/unhealthy exit
            # with the future already built would strand it pending
            # forever — the PR 7 shape future-resolution lints against
            req = _Request(model, data, time.perf_counter(),
                           raw=raw_mode)
            self._pending.append(req)
            self.max_queue_depth = max(self.max_queue_depth,
                                       len(self._pending))
            self._pending_by_model[model] = \
                self._pending_by_model.get(model, 0) + 1
            self._outstanding += 1
            self._idle.clear()
            self._cv.notify_all()
        return req.future

    def drain(self, timeout: float = 60.0) -> None:
        """Block until every submitted request has been harvested."""
        if not self._idle.wait(timeout):
            raise TimeoutError(
                f"serving drain: requests still in flight after {timeout}s")

    # -- dispatcher -----------------------------------------------------
    def _group_ready(self, model: str, max_bucket: int) -> bool:
        return self._pending_by_model.get(model, 0) >= max_bucket

    def _take_group(self, model: str, max_bucket: int) -> list[_Request]:
        """Pop up to max_bucket head-of-line requests for `model`,
        preserving the arrival order of every other model."""
        group, keep = [], deque()
        while self._pending and len(group) < max_bucket:
            # lint: ok(thread-shared-mutation) — caller holds _cv: the
            # dispatcher pops the queue inside its condition-variable
            # span (_dispatch_loop), the discipline LOCK_ORDER documents
            req = self._pending.popleft()
            (group if req.model == model else keep).append(req)
        keep.extend(self._pending)
        # lint: ok(thread-shared-mutation) — caller holds _cv (same
        # contract as the popleft scan above)
        self._pending = keep
        if group:
            left = self._pending_by_model.get(model, 0) - len(group)
            if left > 0:
                # lint: ok(thread-shared-mutation) — caller holds _cv
                # (same contract as the deque scan above)
                self._pending_by_model[model] = left
            else:
                # lint: ok(thread-shared-mutation) — caller holds _cv
                self._pending_by_model.pop(model, None)
        return group

    def _expire(self, group: list[_Request]) -> list[_Request]:
        """Deadline check at window close (ISSUE 12): requests that can
        no longer dispatch within `serve_deadline_ms` of their arrival
        fail with a typed DeadlineError instead of aging further in a
        batch whose result they would discard anyway. Zero cost when
        the knob is off."""
        dl_ms = self._engine.deadline_ms
        if not dl_ms:
            return group
        now = time.perf_counter()
        live = []
        for r in group:
            aged = (now - r.t_enqueue) * 1e3
            if aged > dl_ms:
                self.deadline_count += 1
                self._resolve(r.future, exc=DeadlineError(
                    f"request aged {aged:.0f}ms past "
                    f"serve_deadline_ms={dl_ms:g} before dispatch"))
                self._retire(1)
            else:
                live.append(r)
        return live

    def _dispatch_loop(self) -> None:
        """Crash containment for the dispatcher worker (thread-crash):
        a dispatcher that dies silently parks the whole backlog behind
        a thread that no longer exists — the PR 11 wedge, as a crash.
        A crash fails the in-flight work TYPED, journals, and
        re-enters the loop fresh (the crash consumed at most the group
        it was building; fail_inflight drained the backlog, so a
        deterministic poison request cannot spin this loop)."""
        while True:
            try:
                self._dispatch_forever()
                return      # clean _stop/_draining exit
            except Exception as e:  # the worker must not die silently
                log.exception("serving: dispatcher crashed; failing "
                              "in-flight requests and re-entering")
                self.fail_inflight(EngineUnhealthyError(
                    f"serving dispatcher crashed: {e}"))
                self._engine._journal("serve_dispatcher_crash",
                                      error=str(e))

    def _dispatch_forever(self) -> None:
        while True:
            with self._cv:
                while not self._pending and not self._stop:
                    self._cv.wait()
                if self._stop:
                    return
                head = self._pending[0]
                model = self._engine.model(head.model)
                max_bucket = model.fwd.ladder[-1]
                # batching window: measured from the BATCH's first
                # request; a full max bucket closes the window early.
                # The window is clamped to HALF of serve_deadline_ms so
                # a batch closes with dispatch margin in hand instead
                # of waiting until the exact instant its head request
                # expires (the deadline knob shrinks latency, never
                # adds it).
                window_s = self._engine.window_ms / 1e3
                if self._engine.deadline_ms:
                    window_s = min(window_s,
                                   self._engine.deadline_ms / 2e3)
                deadline = head.t_enqueue + window_s
                while not self._stop and not self._draining:
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0 or \
                            self._group_ready(head.model, max_bucket):
                        break
                    self._cv.wait(timeout=remaining)
                if self._stop:
                    return
                group = self._take_group(head.model, max_bucket)
            group = self._expire(group)
            if group:
                self._dispatch(group)

    @staticmethod
    def _resolve(future: Future, value=None, exc: Exception | None = None
                 ) -> bool:
        """Resolve a request future, tolerating caller-side cancel()
        AND prior resolution: a PENDING future always accepts cancel(),
        so an unconditional set_result would raise InvalidStateError
        and kill this worker thread for every later request — and since
        ISSUE 12 the stall breaker may have ALREADY failed an in-flight
        future from the monitor thread when the late harvest finally
        returns (first resolution wins). Returns True iff this call
        resolved it."""
        if future.done() and not future.cancelled():
            return False  # breaker got there first (skips the CRITICAL
        try:              # log set_running_... emits before raising)
            if future.set_running_or_notify_cancel():
                if exc is not None:
                    future.set_exception(exc)
                else:
                    future.set_result(value)
                return True
        except (InvalidStateError, RuntimeError):
            # already resolved by the breaker (or a racing peer):
            # set_running_or_notify_cancel raises a bare RuntimeError on
            # a FINISHED future (CPython), set_result InvalidStateError
            pass
        return False

    def fail_inflight(self, exc: Exception) -> int:
        """Stall-breaker path (ISSUE 12): fail every dispatched-but-
        unresolved request future with `exc` — AND the whole queued
        backlog, whose dispatcher is the very thread that is wedged (a
        parked request behind a hung dispatch would otherwise stay
        PENDING forever). Called from the watchdog monitor thread.
        In-flight outstanding counts are NOT retired here — if the
        wedged call ever returns, the normal harvest path retires them
        (its own resolves become no-ops); drained backlog entries have
        no other owner, so they retire here."""
        with self._cv:
            groups = [list(g) for g in self._inflight.values()]
            backlog = list(self._pending)
            self._pending.clear()
            self._pending_by_model.clear()
        failed = 0
        for group in groups:
            for r in group:
                if self._resolve(r.future, exc=exc):
                    failed += 1
        for r in backlog:
            if self._resolve(r.future, exc=exc):
                failed += 1
        if backlog:
            self._retire(len(backlog))
        return failed

    def _note_inflight(self, group: list[_Request]) -> int:
        with self._cv:
            token = self._inflight_next
            self._inflight_next += 1
            self._inflight[token] = group
        return token

    def _done_inflight(self, token: int) -> None:
        with self._cv:
            self._inflight.pop(token, None)

    def _dispatch(self, group: list[_Request]) -> None:
        name = group[0].model
        if not self._engine.healthy:
            # breaker open (ISSUE 12): a live dispatcher (e.g. after a
            # HARVEST-section trip) must not keep feeding work into a
            # wedge nobody drains — fail the group typed instead
            exc = EngineUnhealthyError(
                "serving engine unhealthy (dispatch stall breaker "
                "open); request shed")
            for r in group:
                self._resolve(r.future, exc=exc)
            self._retire(len(group))
            return
        try:
            # re-resolve by name: a load_model() reload during the open
            # batching window must dispatch on the CURRENT model, not a
            # retired object (whose residency check could even spill
            # the fresh model to re-upload dead weights)
            model = self._engine.model(name)
        except Exception as e:  # noqa: BLE001 — failures go to callers
            for r in group:
                self._resolve(r.future, exc=e)
            self._retire(len(group))
            return
        # the group was sized by the ladder seen at window-open; a
        # reload may have SHRUNK the max bucket, so chunk to the
        # current one instead of padding a negative dimension
        maxb = model.fwd.ladder[-1]
        for start in range(0, len(group), maxb):
            self._dispatch_one(model, group[start:start + maxb])

    def _materialize(self, model, group: list[_Request]) -> list[_Request]:
        """Window-fused preprocessing (ISSUE 14): deferred raw-decoded
        requests become net input rows HERE, at window granularity — one
        GIL-released native call for the whole group, per-record Python
        fallback for declines (serving/ingest.py). Runs OUTSIDE every
        batcher/engine lock, so handler threads keep submitting and the
        previous batch's device RTT overlaps this window's preprocess.
        A record whose preprocessing fails fails only its OWN future."""
        idx = [i for i, r in enumerate(group) if r.raw]
        if not idx:
            return group
        from . import ingest as _ingest
        rows, errs = _ingest.preprocess_rows(
            model, [group[i].data for i in idx], self._engine.ingest)
        dead = set()
        for j, i in enumerate(idx):
            if errs[j] is not None:
                self._resolve(group[i].future, exc=errs[j])
                self._retire(1)
                dead.add(i)
            else:
                group[i].data = rows[j]
                group[i].raw = False
        if not dead:
            return group
        return [r for i, r in enumerate(group) if i not in dead]

    def _dispatch_one(self, model, group: list[_Request]) -> None:
        from .engine import bucket_for
        group = self._materialize(model, group)
        if not group:
            return
        name = group[0].model
        t0 = time.perf_counter()
        noted = False
        # register BEFORE the device call: a stall inside it is exactly
        # when the breaker needs to find these futures
        token = self._note_inflight(group)
        if not self._engine.healthy:
            # authoritative re-check AFTER registration: a trip between
            # _dispatch's fast-path check and _note_inflight would have
            # snapshotted _inflight without this group — and the
            # monitor thread is gone after its one trip, so a group
            # that slips past here into the device call would hang
            # with no one left to fail it. Post-registration, either
            # this read sees the trip (shed here) or fail_inflight's
            # later snapshot includes the group.
            self._done_inflight(token)
            exc = EngineUnhealthyError(
                "serving engine unhealthy (dispatch stall breaker "
                "open); request shed")
            for r in group:
                self._resolve(r.future, exc=exc)
            self._retire(len(group))
            return
        try:
            batch = np.stack([r.data for r in group]).astype(
                np.float32, copy=False)
            bucket = bucket_for(len(group), model.fwd.ladder)
            padded = model.fwd.pad(batch, bucket)
            # residency check per dispatch: a spilled model re-uploads
            # its weights here (LRU may evict another model's);
            # mark_in_flight pins the model against spilling until the
            # harvest retires the execution. Both the (possible) weight
            # upload and the dispatch sit inside one watchdog section —
            # a hung runtime blocks either the same way.
            with self._engine.dispatch_section(f"dispatch:{name}"):
                # test-only: simulate a hung dispatch (ISSUE 12)
                FAULTS.maybe_stall("serve_dispatch_stall")
                params, state = self._engine._make_resident(
                    model, mark_in_flight=True)
                noted = True
                out = model.fwd.run_bucket(params, state, padded)
        except Exception as e:  # noqa: BLE001 — failures go to callers
            self._done_inflight(token)
            if noted:
                self._engine.note_retire(model)
            log.exception("serving: dispatch failed for model %r", name)
            for r in group:
                self._resolve(r.future, exc=e)
            self._retire(len(group))
            return
        with self._rec_lock:  # stats() iterates this deque concurrently
            self.dispatches.append((name, len(group), bucket))
            self.dispatch_count += 1
        # hand the DEVICE array to the harvester; this thread goes
        # straight back to assembling the next batch
        self._harvest_q.put((group, model, out, t0, time.perf_counter(),
                             token))

    # -- harvester ------------------------------------------------------
    def _harvest_loop(self) -> None:
        while True:
            # lint: ok(deadline-discipline) — idle park by design:
            # close() wakes this queue with a None sentinel, and a
            # wedged materialization is the watchdog's job below
            item = self._harvest_q.get()
            if item is None:
                return
            group, model, out, t_dispatch, t_dispatched, token = item
            try:
                # the harvest thread exists to pay this device->host
                # sync off the dispatch path (watchdog-bounded: a hung
                # runtime blocks the materialization exactly like a
                # dispatch)
                with self._engine.dispatch_section(
                        f"harvest:{group[0].model}"):
                    # lint: ok(host-sync) — out-of-band harvest is the design
                    scores = np.asarray(out)
            except Exception as e:  # noqa: BLE001
                self._done_inflight(token)
                self._engine.note_retire(model)
                for r in group:
                    self._resolve(r.future, exc=e)
                self._retire(len(group))
                continue
            self._done_inflight(token)
            self._engine.note_retire(model)
            t_done = time.perf_counter()
            with self._rec_lock:
                for r in group:
                    self._records.append({
                        "model": r.model,
                        "t_enqueue": r.t_enqueue,
                        "t_done": t_done,
                        "queue_ms": (t_dispatch - r.t_enqueue) * 1e3,
                        "infer_ms": (t_done - t_dispatch) * 1e3,
                        "total_ms": (t_done - r.t_enqueue) * 1e3,
                    })
            # resolve OUTSIDE _rec_lock: set_result runs done-callbacks
            # synchronously in this thread, and a callback reading
            # stats()/records() would re-acquire the non-reentrant lock
            for i, r in enumerate(group):
                self._resolve(r.future, scores[i])
            self._retire(len(group))

    def _retire(self, n: int) -> None:
        with self._cv:
            self._outstanding -= n
            if self._outstanding <= 0:
                self._idle.set()

    def records(self) -> list[dict]:
        with self._rec_lock:
            return list(self._records)

    def dispatch_snapshot(self) -> list[tuple[str, int, int]]:
        with self._rec_lock:
            return list(self.dispatches)
