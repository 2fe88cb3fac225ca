"""Inference engine — AOT-compiled, device-resident model zoo.

Reference: python/caffe/classifier.py + python/caffe/detector.py run
batch inference by padding crops into the deploy net's single static
batch, and examples/web_demo/app.py serves that loop over HTTP one
request at a time; tools/extract_features.cpp is the reference's
"embedding as a service" batch path. All of them pay a full forward at
the prototxt's declared batch no matter how many images arrived, and
the pycaffe surface re-materializes every blob on the host per call.

TPU-native design: inference here is a *pure* path split out of the
training substrate — a deploy NetParameter becomes params plus one
jitted `apply` per **padded shape bucket** (a fixed ladder of batch
sizes, e.g. 1/4/16/max), each AOT-compiled at model load
(`jax.jit(...).lower(...).compile()`), so arrival-size variance never
triggers a recompile: steady-state serving calls only pre-built XLA
executables (`CompileCounter` is the host-side count). Params are
pinned device-resident across requests (re-uploading weights per
request would dwarf compute), and multiple models stay resident under a configurable
HBM budget with LRU spill to the host master copy — spilling drops the
device arrays only, never the compiled executables, so a reload is one
device_put, not a recompile.
"""

from __future__ import annotations

import copy
import logging
import threading
import time
from collections import OrderedDict
from contextlib import nullcontext

import numpy as np

from .. import caffe_io
from ..net import Net
from ..proto.config import NetParameter, ServingParameter
from ..utils.resilience import FAULTS
from .errors import (DeadlineError, EngineClosedError, EngineUnhealthyError,
                     SwapError)

log = logging.getLogger(__name__)

_NULL_SECTION = nullcontext()

# ladder planning moved to the static serving plan (plan.py, ISSUE 17);
# re-exported here so the classic import sites are unchanged
from .plan import DEFAULT_LADDER_GROWTH, bucket_for, plan_ladder  # noqa: E402,F401
from .program_bank import BankStats, ProgramBank, fingerprint  # noqa: E402


class CompileCounter:
    """Counts XLA compiles the serving plane performs. Steady-state
    serving must never move it past the warmed bucket count — the
    zero-recompile claim is `count == warmed buckets`, asserted on CPU
    (tests/test_serving.py) and by `caffe serve -smoke`."""

    def __init__(self):
        self.count = 0
        self._lock = threading.Lock()

    def bump(self) -> None:
        with self._lock:
            self.count += 1


def _tree_bytes(tree) -> int:
    import jax
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree)
               if hasattr(a, "dtype"))


def _device_probe(timeout: float) -> bool:
    """One tiny device round-trip in a side thread, bounded by
    `timeout`: True iff the device answered in time. The work runs in
    its own daemon thread because a hung device call sits INSIDE C++
    where no Python signal can interrupt — the probe
    thread is then leaked-but-bounded while the caller returns False."""
    done = threading.Event()
    ok: list[bool] = []

    def work():
        try:
            import jax
            x = jax.device_put(np.ones((8,), np.float32))
            # a real round-trip, not just an enqueue
            np.asarray(x + 1.0)
            ok.append(True)
        # lint: ok(typed-failure) — any failure = not recovered; the
        # finally sets the done event the prober decision waits on
        except Exception:  # noqa: BLE001 — any failure = not recovered
            pass
        finally:
            done.set()

    threading.Thread(target=work, daemon=True,
                     name="serve-device-probe").start()
    return done.wait(timeout) and bool(ok)


def _poison_first_leaf(tree):
    """Test-only (swap_canary_bad fault site): NaN the first float leaf
    of a host params tree so the canary gate must reject it."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    for i, leaf in enumerate(leaves):
        if hasattr(leaf, "dtype") and np.issubdtype(leaf.dtype,
                                                    np.floating):
            # lint: ok(host-sync) — host master tree, fault-injection only
            bad = np.array(leaf, copy=True)
            bad.flat[0] = np.nan
            leaves[i] = bad
            break
    return jax.tree_util.tree_unflatten(treedef, leaves)


class BucketedForward:
    """Padded static-batch forward over a bucket ladder.

    One deploy NetParameter, one compiled XLA program per bucket size
    (the Input batch dim rewritten per bucket; layer params are
    shape-identical across buckets, so one params tree serves all).
    Shared by the serving engine and by Classifier/Detector
    (classifier.py) so both surfaces run the exact same programs.
    """

    def __init__(self, net_param: NetParameter, *, ladder=None,
                 max_batch: int = 0, out_blob: str | None = None,
                 model_dir: str = "", counter: CompileCounter | None = None,
                 full_env: bool = False, dtype: str = "f32",
                 bank: ProgramBank | None = None,
                 bank_stats: BankStats | None = None):
        self._base = copy.deepcopy(net_param)
        self._model_dir = model_dir
        # serve_dtype (ISSUE 9): "bf16" compiles every bucket program
        # with the net-level bf16 precision override (activations
        # compute in bfloat16 on the MXU's native 16-bit path) and casts
        # the output blob back to f32 at the program boundary — scores
        # stay f32 ndarrays for every caller. A dtype is fixed at
        # construction, so the ladder still compiles exactly once per
        # bucket: steady-state serving performs ZERO compiles either
        # way.
        if dtype not in ("", "f32", "bf16"):
            raise ValueError(f"unknown serve_dtype {dtype!r} "
                             "(expected 'f32' or 'bf16')")
        self._precision = "" if dtype in ("", "f32") else dtype
        declared = self._declared_batch(self._base)
        self.max_batch = max_batch or declared
        self.ladder = plan_ladder(self.max_batch, ladder)
        self.counter = counter or CompileCounter()
        # program bank (ISSUE 17): warm tries a deserialize before
        # compiling; every real compile is counted as a bank miss even
        # bank-off, so `compile_count == bank_misses` holds everywhere
        self._bank = bank
        self._bank_stats = bank_stats or (bank.stats if bank is not None
                                          else BankStats())
        # per-bucket warm breakdown (lower/compile/deserialize ms),
        # appended under _lock, surfaced via engine.stats()["bank"]
        self.warm_events: list[dict] = []
        self._nets: dict[int, Net] = {}
        self._compiled: dict[int, object] = {}
        self._out_blob = out_blob
        self._lock = threading.Lock()
        # full_env: programs return the whole blob environment instead
        # of just the output blob — the pycaffe surface (classifier.py)
        # needs net.blobs populated after predict(); serving keeps the
        # single-output programs
        self._full_env = full_env
        self.last_env = None  # most recent bucket's env (full_env only)

    @staticmethod
    def _declared_batch(param: NetParameter) -> int:
        from ..proto.upgrade import normalize_net
        param = normalize_net(copy.deepcopy(param))
        for lp in param.layer:
            if lp.type == "Input" and lp.input_param and lp.input_param.shape:
                dims = lp.input_param.shape[0].dim
                if dims:
                    return int(dims[0])
        raise ValueError("deploy net has no Input layer with a declared "
                         "shape; serving needs a deploy prototxt")

    def _net_for(self, bucket: int) -> Net:
        net = self._nets.get(bucket)
        if net is None:
            param = copy.deepcopy(self._base)
            from ..proto.upgrade import normalize_net
            param = normalize_net(param)
            for lp in param.layer:
                if lp.type == "Input" and lp.input_param:
                    for shape in lp.input_param.shape:
                        if shape.dim:
                            shape.dim[0] = bucket
            net = Net(param, phase="TEST", model_dir=self._model_dir,
                      device_transform=False, precision=self._precision)
            if len(net.feed_blobs) != 1:
                raise ValueError(
                    f"serving needs exactly one input blob, deploy net "
                    f"declares {net.feed_blobs}")
            self._nets[bucket] = net
        return net

    def init(self, seed: int = 0):
        """Fresh (params, state) for this architecture — bucket-size
        independent, so any bucket net can mint them."""
        import jax
        net = self._net_for(self.ladder[0])
        return net.init(jax.random.PRNGKey(seed))

    def out_blob(self, bucket: int | None = None) -> str:
        if self._out_blob is None:
            net = self._net_for(bucket or self.ladder[0])
            consumed = {b for l in net.layers for b in l.lp.bottom}
            outs = [t for l in net.layers for t in l.lp.top
                    if t not in consumed]
            self._out_blob = outs[-1]
        return self._out_blob

    def input_blob(self) -> str:
        return self._net_for(self.ladder[0]).feed_blobs[0]

    def input_shape(self, bucket: int | None = None) -> tuple:
        net = self._net_for(bucket or self.ladder[0])
        return net.blob_shapes[net.feed_blobs[0]]

    def compile_bucket(self, bucket: int, params, state):
        """AOT-build this bucket's program (idempotent): a verified
        program-bank entry deserializes — an UNCOUNTED compile and a
        counted bank hit — anything else compiles fresh (counted, and
        counted as a bank miss; with the bank off every build is a
        miss, so `compile_count == bank_misses` holds unconditionally).
        Each build appends a warm event with its lower/compile/
        deserialize breakdown for the cold-start telemetry."""
        import jax
        with self._lock:
            compiled = self._compiled.get(bucket)
            if compiled is not None:
                return compiled
            net = self._net_for(bucket)
            in_blob, out = net.feed_blobs[0], self.out_blob(bucket)
            ev = {"bucket": bucket, "source": "compile", "lower_ms": 0.0,
                  "compile_ms": 0.0, "deserialize_ms": 0.0}
            fp = None
            if self._bank is not None:
                fp = fingerprint(
                    self._base, bucket=bucket,
                    dtype=self._precision or "f32",
                    out_spec="env" if self._full_env else out,
                    runtime=self._bank.runtime())
                t0 = time.perf_counter()
                compiled = self._bank.load(fp)
                ev["deserialize_ms"] = round(
                    (time.perf_counter() - t0) * 1e3, 3)
                if compiled is not None:
                    ev["source"] = "bank"
                    self.warm_events.append(ev)
                    self._compiled[bucket] = compiled
                    return compiled
            else:
                self._bank_stats.bump("misses")

            def fwd(p, s, feeds):
                env, _, _ = net.apply(p, s, feeds, train=False)
                if self._full_env:
                    return dict(env)
                res = env[out]
                if res.dtype != np.float32:
                    # bf16 bucket programs hand callers f32 scores — the
                    # classify/detect row contract is dtype-stable
                    res = res.astype(np.float32)
                return res

            feeds_struct = {in_blob: jax.ShapeDtypeStruct(
                net.blob_shapes[in_blob], np.float32)}
            t0 = time.perf_counter()
            lowered = jax.jit(fwd).lower(params, state, feeds_struct)
            t1 = time.perf_counter()
            # lint: ok(blocking-under-lock) — serializing the compile IS
            # this lock's purpose: racing warmers must not build the same
            # bucket program twice, and steady-state serving never takes
            # this path (compile_count == warmed_buckets is the invariant)
            compiled = lowered.compile()
            ev["lower_ms"] = round((t1 - t0) * 1e3, 3)
            ev["compile_ms"] = round((time.perf_counter() - t1) * 1e3, 3)
            self.counter.bump()
            if fp is not None:
                # repopulate after the counted miss, so the NEXT start
                # is the bank-warm one (rotten entries self-heal)
                self._bank.store(fp, compiled)
            self.warm_events.append(ev)
            self._compiled[bucket] = compiled
            return compiled

    def warm(self, params, state) -> int:
        """Compile every ladder bucket ahead of traffic; returns the
        number of warmed programs (== len(ladder))."""
        for b in self.ladder:
            self.compile_bucket(b, params, state)
        return len(self.ladder)

    def run_bucket(self, params, state, batch: np.ndarray):
        """Dispatch one padded bucket; returns the DEVICE output array
        (not harvested — the caller overlaps np.asarray with the next
        batch's assembly). batch.shape[0] must be a ladder bucket."""
        bucket = int(batch.shape[0])
        compiled = self._compiled.get(bucket)
        if compiled is None:
            # cold path: only reachable when warm() was skipped — counted,
            # so the zero-recompile assertion catches any steady-state use
            compiled = self.compile_bucket(bucket, params, state)
        in_blob = self.input_blob()
        return compiled(params, state, {in_blob: batch})

    @staticmethod
    def pad(chunk: np.ndarray, bucket: int) -> np.ndarray:
        if len(chunk) == bucket:
            return chunk
        pad = np.zeros((bucket - len(chunk), *chunk.shape[1:]), chunk.dtype)
        return np.concatenate([chunk, pad])

    def forward(self, params, state, data: np.ndarray) -> np.ndarray:
        """Synchronous padded-bucket forward over N preprocessed images:
        greedy max-bucket chunks, the tail rounded up to its smallest
        bucket. Row-identical to the classic pad-to-declared-batch loop
        (rows are batch-independent at inference: conv/ip/softmax are
        per-row, BatchNorm uses running stats)."""
        data = np.asarray(data, np.float32)
        preds = []
        start = 0
        while start < len(data):
            take = min(len(data) - start, self.ladder[-1])
            chunk = data[start:start + take]
            padded = self.pad(chunk, bucket_for(take, self.ladder))
            out = self.run_bucket(params, state, padded)
            if self._full_env:
                self.last_env = out
                out = out[self.out_blob()]
            # the synchronous surface harvests one bucket per chunk by
            # contract; async callers use run_bucket + the harvest thread
            # lint: ok(host-sync) — deliberate per-bucket harvest
            preds.append(np.asarray(out)[:take])
            start += take
        return np.concatenate(preds)


class InferenceModel:
    """One servable model: deploy prototxt -> host master weights +
    bucketed AOT programs + preprocessing (classifier.py Transformer
    conventions), residency-managed by the engine."""

    def __init__(self, name: str, model_file: str, weights: str | None = None,
                 *, ladder=None, max_batch: int = 0, mean=None,
                 input_scale=None, raw_scale=None, channel_swap=None,
                 image_dims=None, counter: CompileCounter | None = None,
                 model_dir: str = "", dtype: str = "f32",
                 bank: ProgramBank | None = None,
                 bank_stats: BankStats | None = None):
        import jax
        self.name = name
        param = NetParameter.from_file(model_file)
        self.fwd = BucketedForward(param, ladder=ladder, max_batch=max_batch,
                                   counter=counter, model_dir=model_dir,
                                   dtype=dtype, bank=bank,
                                   bank_stats=bank_stats)
        params, state = self.fwd.init()
        if weights:
            from .. import io as _io
            net = self.fwd._net_for(self.fwd.ladder[0])
            params, state = net.import_weights(params, state,
                                               _io.load_weights(weights))
        # host master copy — the spill target; device residency is a
        # device_put of exactly this tree
        self.params_host = jax.tree_util.tree_map(np.asarray, params)
        self.state_host = jax.tree_util.tree_map(np.asarray, state)
        self.param_bytes = _tree_bytes(self.params_host) \
            + _tree_bytes(self.state_host)
        self._resident: tuple | None = None
        self._upload_lock = threading.Lock()
        self.was_spilled = False
        # dispatches in flight on this model's device arrays (engine
        # _lock guards it): spilling while > 0 frees nothing — the
        # execution holds the buffers — so the LRU defers such victims
        self.in_flight = 0

        in_shape = self.fwd.input_shape()
        in_blob = self.fwd.input_blob()
        self.crop_dims = np.array(in_shape[2:]) if len(in_shape) == 4 \
            else None
        self.image_dims = np.array(image_dims) if image_dims is not None \
            else self.crop_dims
        self.transformer = caffe_io.Transformer.for_input(
            in_blob, in_shape,
            transpose=(2, 0, 1) if len(in_shape) == 4 else None,
            mean=mean, input_scale=input_scale, raw_scale=raw_scale,
            channel_swap=channel_swap)
        # native window-preprocess spec (ISSUE 14, serving/ingest.py):
        # None when this model's preprocessing is not expressible in the
        # fused kernel — its requests keep the classic per-request path
        from . import ingest as _ingest
        self.ingest_plan = _ingest.build_plan(self)

    # -- residency ------------------------------------------------------
    @property
    def resident(self) -> bool:
        return self._resident is not None

    def ensure_resident(self):
        """Device-resident (params, state); uploads the host master copy
        on first touch / after a spill. Compiled programs are untouched
        either way — residency is data movement, never compilation.
        Serialized per model: two threads racing here (dispatcher +
        load_model) must not pay the multi-second upload twice."""
        with self._upload_lock:
            if self._resident is None:
                import jax
                # lint: ok(blocking-under-lock) — upload serialization is
                # this per-model lock's purpose (two racers must not pay
                # the multi-second device_put twice); engine._lock is
                # NEVER held here (LOCK_ORDER: _upload_lock -> _lock), so
                # the stall is private to this model's upload
                self._resident = (jax.device_put(self.params_host),
                                  jax.device_put(self.state_host))
            return self._resident

    def spill(self) -> None:
        """Drop the device copy (HBM freed once in-flight work retires);
        the host master copy and every compiled program survive."""
        self._resident = None
        self.was_spilled = True

    # -- preprocessing --------------------------------------------------
    def preprocess(self, img: np.ndarray) -> np.ndarray:
        """HWC float image in [0,1] -> the net's input row (resize to
        image_dims, center-crop to crop_dims, Transformer pipeline) —
        the Classifier.predict(oversample=False) recipe."""
        in_blob = self.fwd.input_blob()
        if self.crop_dims is None:
            return np.asarray(img, np.float32).reshape(
                self.fwd.input_shape()[1:])
        im = caffe_io.resize_center_crop(img, self.image_dims,
                                         self.crop_dims)
        return self.transformer.preprocess(in_blob, im)


class ServingEngine:
    """Multi-model residency + continuous batching + telemetry.

    Knobs (ServingParameter, docs/serving.md): `serve_window_ms` —
    batching window; `serve_buckets` — explicit bucket ladder;
    `serve_hbm_mb` — HBM budget for resident weights (0 = unlimited),
    enforced by LRU spill; and the resilience trio (ISSUE 12):
    `serve_queue_limit` — bounded backlog, over-limit submits shed with
    a typed ShedError; `serve_deadline_ms` — per-request dispatch
    deadline (DeadlineError at window close instead of aging forever);
    `serve_stall_s` — dispatch stall breaker (a device call past it
    fails the in-flight futures, journals, and flips the engine
    unhealthy so requests shed instead of queueing behind the hang);
    `serve_program_bank` (ISSUE 17) — directory of serialized bucket
    executables: a bank-warm start deserializes its whole ladder with
    zero compiles (`compile_count == bank_misses`), empty = off.

    `journal` names a prefix for the serving run journal
    (`<journal>.serve.run.json` — breaker trips, hot swaps, swap
    rejections, shutdown); None (library default) journals nothing.
    """

    def __init__(self, serving_param: ServingParameter | None = None, *,
                 window_ms: float | None = None, hbm_mb: float | None = None,
                 buckets=None, queue_limit: int | None = None,
                 deadline_ms: float | None = None,
                 stall_s: float | None = None, journal: str | None = None,
                 decoded_cache_mb: float | None = None,
                 program_bank: str | None = None,
                 start: bool = True):
        # AOT warms go through the persistent XLA cache: a restarted
        # server re-loads its zoo from disk hits, not fresh compiles
        from ..utils.compile_cache import enable_compile_cache
        enable_compile_cache()
        sp = serving_param or ServingParameter()
        self.window_ms = float(window_ms if window_ms is not None
                               else sp.serve_window_ms)
        budget_mb = float(hbm_mb if hbm_mb is not None else sp.serve_hbm_mb)
        # reject nonsense at init like the other perf knobs (ISSUE 6
        # convention): a negative budget would otherwise read as a
        # never-satisfiable LRU target = perpetual spill thrash
        if self.window_ms < 0:
            raise ValueError(
                f"serve_window_ms must be >= 0, got {self.window_ms}")
        if budget_mb < 0:
            raise ValueError(
                f"serve_hbm_mb must be >= 0 (0 = unlimited), "
                f"got {budget_mb}")
        self.hbm_budget = int(budget_mb * 2**20)  # 0 = unlimited
        # resilience knobs (ISSUE 12) — all 0 = off = prior behavior
        self.queue_limit = int(queue_limit if queue_limit is not None
                               else sp.serve_queue_limit)
        self.deadline_ms = float(deadline_ms if deadline_ms is not None
                                 else sp.serve_deadline_ms)
        self.stall_s = float(stall_s if stall_s is not None
                             else sp.serve_stall_s)
        if self.queue_limit < 0:
            raise ValueError(
                f"serve_queue_limit must be >= 0 (0 = unbounded), "
                f"got {self.queue_limit}")
        if self.deadline_ms < 0:
            raise ValueError(
                f"serve_deadline_ms must be >= 0 (0 = no deadline), "
                f"got {self.deadline_ms}")
        if self.stall_s < 0:
            raise ValueError(
                f"serve_stall_s must be >= 0 (0 = breaker off), "
                f"got {self.stall_s}")
        # request-ingest plane (ISSUE 14): native decode + window-fused
        # preprocessing + the crc32c-keyed hot-content decoded cache
        cache_mb = float(decoded_cache_mb if decoded_cache_mb is not None
                         else sp.serve_decoded_cache_mb)
        if cache_mb < 0:
            raise ValueError(
                f"serve_decoded_cache_mb must be >= 0 (0 = cache off), "
                f"got {cache_mb}")
        from .ingest import RequestIngest
        self.ingest = RequestIngest(cache_mb)
        self.journal_prefix = journal
        self.ladder_spec = buckets if buckets is not None \
            else (sp.serve_buckets or None)
        # serve_dtype (ISSUE 9): compute precision for every model's
        # bucket programs; validated here like the other serving knobs
        self.serve_dtype = str(getattr(sp, "serve_dtype", "") or "f32")
        if self.serve_dtype not in ("f32", "bf16"):
            raise ValueError(
                f"unknown serve_dtype {self.serve_dtype!r} (expected "
                "'f32' or 'bf16')")
        self.counter = CompileCounter()
        # persistent AOT program bank (ISSUE 17): serve_program_bank
        # names the bank directory, empty = off. The stats object lives
        # on the ENGINE either way, so `compile_count == bank_misses`
        # is an unconditional invariant (bank off: every warm compiles
        # and counts a miss; bank-warm: both are zero).
        bank_path = str(program_bank if program_bank is not None
                        else getattr(sp, "serve_program_bank", "") or "")
        self.bank_stats = BankStats()
        self.bank = ProgramBank(bank_path, self.bank_stats) \
            if bank_path else None
        # cold-start telemetry: wall time spent in load_model (plan +
        # init + warm + upload), summed across the zoo
        self.cold_start_ms = 0.0
        self._plans: OrderedDict[str, dict] = OrderedDict()  # load order
        self._models: OrderedDict[str, InferenceModel] = OrderedDict()
        self._lock = threading.RLock()
        self.spills = 0
        self.reloads = 0
        # buckets warmed by models since REPLACED via load_model(same
        # name): their compiles stay in the counter, so the invariant
        # counts them on the warmed side too
        self._retired_warmed = 0
        # ladder buckets a load_model currently in flight will warm
        self._pending_warm = 0
        # models whose device upload is in flight (resident for budget
        # math, but not yet spillable)
        self._uploading: set[str] = set()
        # stall breaker state (ISSUE 12): flipped unhealthy by the
        # watchdog monitor thread, back healthy by a recovery probe
        self._healthy = True
        self._closed = False
        self._breaker: dict | None = None  # last trip / recovery record
        self._watchdog = None
        self._probe_lock = threading.Lock()
        self._last_probe = 0.0
        self.stall_trips = 0
        self.unhealthy_sheds = 0
        self.swaps = 0
        self.swap_rejections = 0
        self.last_activity = time.monotonic()
        if self.stall_s > 0:
            self._arm_breaker()
        from .batcher import Batcher
        self._batcher = Batcher(self)
        if start:
            self._batcher.start()

    # -- model zoo ------------------------------------------------------
    def load_model(self, name: str, model_file: str,
                   weights: str | None = None, **preprocess) -> InferenceModel:
        """Load + AOT-warm a model: every ladder bucket builds NOW, so
        steady-state traffic of any arrival-size mix runs zero compiles
        — and with a warm program bank the build itself deserializes
        instead of compiling (zero compiles at load, ISSUE 17)."""
        t_load = time.perf_counter()
        # static plan FIRST, before any device touch: the
        # netshape engine prices the ladder's activation bytes and the
        # model's param bytes jax-free (plan.py), so admission and the
        # LRU spill order are decided before the backend starts;
        # planning failure must never block serving
        plan = None
        try:
            from .plan import plan_model
            plan = plan_model(
                NetParameter.from_file(model_file),
                ladder=self.ladder_spec,
                max_batch=int(preprocess.get("max_batch", 0) or 0),
                dtype=self.serve_dtype)
        # lint: ok(typed-failure) — the plan is advisory telemetry;
        # serving is fully correct without it (docstring contract)
        except Exception as e:  # noqa: BLE001 — plan is advisory
            log.warning("serving: static plan for %r failed (%s); "
                        "loading without one", name, e)
        model = InferenceModel(
            name, model_file, weights, ladder=self.ladder_spec,
            counter=self.counter, dtype=self.serve_dtype,
            bank=self.bank, bank_stats=self.bank_stats, **preprocess)
        # count the incoming ladder on the warmed side BEFORE warming:
        # warm bumps the shared counter per bucket, and a /stats poll
        # mid-load must not read compile_count > warmed_buckets as a
        # false steady-state recompile
        with self._lock:
            self._pending_warm += len(model.fwd.ladder)
        try:
            model.fwd.warm(model.params_host, model.state_host)
        except BaseException:
            with self._lock:
                self._pending_warm -= len(model.fwd.ladder)
                # a partial warm's compiles stay in the counter forever
                self._retired_warmed += len(model.fwd._compiled)
            raise
        with self._lock:
            self._pending_warm -= len(model.fwd.ladder)
            old = self._models.get(name)
            if old is not None:
                self._retired_warmed += len(old.fwd.ladder)
            self._models[name] = model
            if plan is not None:
                self._plans[name] = plan
        self._make_resident(model)
        load_ms = round((time.perf_counter() - t_load) * 1e3, 3)
        with self._lock:
            self.cold_start_ms += load_ms
            if plan is not None:
                plan["load_ms"] = load_ms
        log.info("serving: model %r loaded in %.0f ms (%d bucket "
                 "programs %s, %.1f MiB params)", name, load_ms,
                 len(model.fwd.ladder), model.fwd.ladder,
                 model.param_bytes / 2**20)
        return model

    def model(self, name: str) -> InferenceModel:
        with self._lock:
            return self._models[name]

    @property
    def models(self) -> list[str]:
        with self._lock:
            return list(self._models)

    @property
    def compile_count(self) -> int:
        return self.counter.count

    @property
    def bank_hits(self) -> int:
        return self.bank_stats.hits

    @property
    def bank_misses(self) -> int:
        return self.bank_stats.misses

    @property
    def warmed_buckets(self) -> int:
        with self._lock:
            return self._retired_warmed + self._pending_warm + sum(
                len(m.fwd.ladder) for m in self._models.values())

    def _make_resident(self, model: InferenceModel, *,
                       mark_in_flight: bool = False):
        """LRU admission: spill least-recently-used resident models until
        `model` fits the HBM budget, then upload. A single model larger
        than the whole budget stays resident with a warning (serving it
        from host per request would pay the weight upload every batch).
        mark_in_flight (the dispatcher) increments model.in_flight in
        the same locked section that releases the upload reservation, so
        the LRU can never observe a dispatch-bound model as spillable."""
        with self._lock:
            self._models.move_to_end(model.name)  # most recently used
            # a model mid-upload elsewhere already counts as resident:
            # its HBM is committed even though _resident is not set yet
            was_resident = model.resident or model.name in self._uploading
            if not was_resident and self.hbm_budget:
                charged = [m for m in self._models.values()
                           if (m.resident or m.name in self._uploading)
                           and m is not model]
                used = sum(m.param_bytes for m in charged)
                deferred = False
                for victim in charged:  # OrderedDict order = LRU first
                    if used + model.param_bytes <= self.hbm_budget:
                        break
                    if victim.name in self._uploading \
                            or victim.in_flight > 0:
                        # spilling frees nothing while an upload or a
                        # dispatched execution still holds the buffers
                        # — crediting the budget here would over-commit
                        # real HBM
                        deferred = True
                        continue
                    victim.spill()
                    self.spills += 1
                    used -= victim.param_bytes
                    log.info("serving: spilled %r (%.1f MiB) for %r",
                             victim.name, victim.param_bytes / 2**20,
                             model.name)
                if used + model.param_bytes > self.hbm_budget:
                    if deferred:
                        log.warning(
                            "serving: HBM budget transiently "
                            "over-committed admitting %r (victims "
                            "mid-upload or mid-dispatch cannot free "
                            "HBM; reclaimed at their next LRU pass)",
                            model.name)
                    else:
                        log.warning(
                            "serving: model %r (%.1f MiB) alone exceeds "
                            "the %.1f MiB HBM budget; keeping it "
                            "resident anyway",
                            model.name, model.param_bytes / 2**20,
                            self.hbm_budget / 2**20)
            if not was_resident and model.was_spilled:
                self.reloads += 1
            self._uploading.add(model.name)
        # upload OUTSIDE the engine lock: a weight device_put can take
        # seconds, and the dispatcher resolves models
        # (engine.model -> this lock) while holding the batcher's
        # condition variable — holding _lock here would stall every
        # submit() across all models for the whole upload
        try:
            res = model.ensure_resident()
        except BaseException:
            with self._lock:
                self._uploading.discard(model.name)
            raise
        with self._lock:
            # hand off the _uploading reservation to the in_flight mark
            # ATOMICALLY: a window where the model holds neither would
            # let a concurrent LRU pass spill it and credit HBM the
            # about-to-run dispatch still occupies
            if mark_in_flight:
                model.in_flight += 1
            self._uploading.discard(model.name)
        return res

    def note_retire(self, model: InferenceModel) -> None:
        """Batcher bookkeeping: the dispatch marked in flight by
        `_make_resident(mark_in_flight=True)` has harvested (or failed);
        its device arrays no longer pin the model's HBM."""
        with self._lock:
            model.in_flight -= 1
            self.last_activity = time.monotonic()

    # -- stall breaker (ISSUE 12) ---------------------------------------
    def _arm_breaker(self) -> None:
        from ..utils.resilience import DispatchWatchdog
        # lint: ok(thread-shared-mutation) — callers serialize: __init__
        # runs before any thread exists, probe_recovery holds _probe_lock,
        # and _stop_breaker (the only other writer) takes _probe_lock too
        self._watchdog = DispatchWatchdog(
            self.stall_s, on_timeout=self._on_stall, hard_exit=False)

    def dispatch_section(self, label: str):
        """Watchdog section for one device-blocking serving call
        (dispatch / harvest) — a no-op context when the breaker is off."""
        wd = self._watchdog
        return _NULL_SECTION if wd is None else wd.section(label)

    def _on_stall(self, label: str, elapsed: float) -> None:
        """Watchdog monitor callback: a serving device call blew past
        `serve_stall_s`. The hung thread cannot be interrupted (it
        sits inside C++), but its FUTURES can be
        failed from here — clients get a bounded DeadlineError while
        the engine flips unhealthy and sheds new requests instead of
        queueing them behind the wedge."""
        self._healthy = False
        self.stall_trips += 1
        self._breaker = {"state": "open", "section": label,
                         "elapsed_s": round(elapsed, 1),
                         "time": time.time()}
        log.error("serving: %s stalled %.1fs past the %.1fs breaker "
                  "deadline — failing in-flight futures, shedding new "
                  "requests until a recovery probe succeeds",
                  label, elapsed, self.stall_s)
        self._journal(f"serve_stall:{label}", elapsed_s=round(elapsed, 1),
                      stall_s=self.stall_s)
        failed = self._batcher.fail_inflight(DeadlineError(
            f"serving dispatch {label!r} stalled past "
            f"serve_stall_s={self.stall_s:g}s; engine unhealthy"))
        if failed:
            log.error("serving: failed %d in-flight request future(s) "
                      "after the stall", failed)

    def probe_recovery(self, timeout: float | None = None) -> bool:
        """Try to close the breaker: verify the stalled call actually
        retired (a section still open means the wedge never returned —
        only a process restart clears that) and that a fresh tiny
        device round-trip completes within `timeout` (default
        `serve_stall_s`). On success the watchdog is re-armed (a trip
        ends its monitor thread), worker threads that died are
        respawned, and the engine serves again."""
        if self._healthy:
            return True
        with self._probe_lock:
            if self._healthy:
                return True
            if self._closed:
                # a probe thread that lost the race with close() must
                # not re-arm a fresh watchdog (a monitor thread nobody
                # would ever stop) or flip a closed engine healthy
                return False
            self._last_probe = time.monotonic()
            wd = self._watchdog
            if wd is not None:
                still_open = wd.open_sections()
                if still_open:
                    log.warning(
                        "serving: recovery probe refused — stalled "
                        "section %r never returned (a wedged device "
                        "call cannot be reclaimed in-process)",
                        still_open[0])
                    return False
            if not _device_probe(timeout if timeout is not None
                                 else max(self.stall_s, 1.0)):
                log.warning("serving: recovery probe failed; breaker "
                            "stays open")
                return False
            if self._closed:
                # defense in depth: _mark_closed publishes under
                # _probe_lock, so this is unreachable while we hold it
                # — kept against a future lock-free _closed writer
                return False
            if wd is not None:
                wd.stop()
            self._arm_breaker()
            self._batcher.ensure_threads()
            self._breaker = {"state": "closed", "recovered": time.time(),
                             "trips": self.stall_trips}
            self._healthy = True
            log.info("serving: recovery probe succeeded; breaker closed")
            self._journal("serve_recovered", trips=self.stall_trips)
            return True

    def _probe_recovery_guarded(self) -> None:
        """Thread entry for the async recovery probe (thread-crash):
        a probe that raises must journal, not die silently — a silent
        death here leaves the breaker open with no operator signal."""
        try:
            self.probe_recovery()
        except Exception as e:
            log.exception("serving: recovery probe crashed")
            self._journal("serve_probe_crash", error=str(e))

    def _maybe_probe_async(self) -> None:
        """Kick a background recovery probe at most once per breaker
        deadline — live traffic keeps probing a hung device without any
        operator action, and without stacking probe threads."""
        now = time.monotonic()
        if now - self._last_probe < max(self.stall_s, 1.0):
            return
        # lint: ok(thread-shared-mutation) — deliberate lock-free
        # throttle: taking _probe_lock here would park every submit()
        # caller behind an in-flight recovery probe for up to stall_s;
        # the worst a lost race costs is one redundant probe thread,
        # and probe_recovery itself serializes under _probe_lock
        self._last_probe = now
        threading.Thread(target=self._probe_recovery_guarded, daemon=True,
                         name="serve-recovery-probe").start()

    def note_unhealthy_shed(self) -> None:
        with self._lock:
            self.unhealthy_sheds += 1

    @property
    def healthy(self) -> bool:
        return self._healthy

    def health(self) -> dict:
        """/healthz payload: breaker state + last-dispatch age."""
        idle = time.monotonic() - self.last_activity
        return {
            "healthy": self._healthy,
            "breaker": self._breaker or {"state": "closed", "trips": 0},
            "stall_trips": self.stall_trips,
            "last_dispatch_age_s": round(idle, 3),
            "stall_s": self.stall_s,
        }

    def ready(self) -> tuple[bool, dict]:
        """/readyz payload: ready iff the zoo is loaded and fully
        AOT-warmed — every warmed bucket was either compiled or
        deserialized from the program bank (`compile_count ==
        bank_misses` and `compile_count + bank_hits == warmed_buckets`;
        bank off, hits are zero and this is exactly the classic
        `compile_count == warmed_buckets`), no load in flight, the
        breaker closed, and the engine accepting work."""
        with self._lock:
            warming = self._pending_warm > 0
            models = len(self._models)
        doc = {
            "models": models,
            "warming": warming,
            "warmed_buckets": self.warmed_buckets,
            "compile_count": self.compile_count,
            "bank_hits": self.bank_hits,
            "bank_misses": self.bank_misses,
            "healthy": self._healthy,
            "closed": self._closed,
        }
        doc["ready"] = (models > 0 and not warming and not self._closed
                        and self._healthy
                        and self.compile_count == doc["bank_misses"]
                        and self.compile_count + doc["bank_hits"]
                        == doc["warmed_buckets"])
        return doc["ready"], doc

    def _journal(self, reason: str, **extra) -> None:
        """Serving run journal (`<journal>.serve.run.json`): breaker
        trips, swaps, swap rejections, shutdown. Best-effort — a
        journaling failure must never take serving down."""
        if not self.journal_prefix:
            return
        try:
            from ..utils import resilience
            resilience.write_run_manifest(
                self.journal_prefix + ".serve", reason=reason, **extra)
        except OSError:
            log.exception("serving: run journal failed (continuing)")

    # -- verified hot-swap (ISSUE 12) -----------------------------------
    def swap_weights(self, name: str, weights: str, *,
                     canary: bool = True, source: str = "") -> None:
        """Live-reload `name`'s weights from `weights` WITHOUT touching
        its compiled bucket programs: the params tree is shape-identical
        across weight files of one architecture, so a hot swap is a
        host-side import + one device upload — never a recompile
        (`compile_count` unchanged: tests/test_serving_resilience.py,
        tools/serve_watch_smoke.py).

        The canary gate runs the smallest already-compiled bucket with
        the CANDIDATE weights before anything reaches the serving path:
        non-finite scores, wrong shapes, or an unloadable weights file
        raise SwapError and the previous weights keep serving untouched
        (rollback by staging). Callers that verified the snapshot bytes
        first (serving/watch.py via resilience.verify_snapshot) get the
        full train->serve trust chain."""
        model = self.model(name)  # KeyError for unknown models
        import jax
        try:
            from .. import io as _io
            net = model.fwd._net_for(model.fwd.ladder[0])
            params0, state0 = model.fwd.init()
            params, state = net.import_weights(params0, state0,
                                               _io.load_weights(weights))
            params_host = jax.tree_util.tree_map(np.asarray, params)
            state_host = jax.tree_util.tree_map(np.asarray, state)
        except SwapError:
            raise
        except Exception as e:  # noqa: BLE001 — typed for the watcher
            self.note_swap_rejected(name, f"weights load failed: {e}",
                                    source=source)
            raise SwapError(
                f"hot-swap candidate {weights!r} failed to load: {e}"
            ) from e
        if FAULTS.fire("swap_canary_bad") is not None:
            # test-only: rot the candidate so the canary must catch it
            params_host = _poison_first_leaf(params_host)
        if canary:
            try:
                self._canary_gate(model, params_host, state_host)
            except SwapError as e:
                self.note_swap_rejected(name, str(e), source=source)
                raise
        # upload OUTSIDE the lock (the _make_resident recipe): a weight
        # device_put can take seconds, and a dispatcher
        # blocked on _upload_lock inside its watchdog section for that
        # long would false-trip the stall breaker on a healthy device.
        # Only a CURRENTLY-RESIDENT model gets the eager upload (the
        # new copy transiently coexists with the old until in-flight
        # work retires — same bounded over-commit class as the LRU's
        # in-flight deferrals); a spilled model commits its host trees
        # alone and pays the upload at its next ensure_resident,
        # through the budget-enforcing residency path, instead of a
        # seconds-long device_put that would be dropped on commit.
        with model._upload_lock:
            resident_now = model._resident is not None
        uploaded = None
        if resident_now:
            uploaded = (jax.device_put(params_host),
                        jax.device_put(state_host))
        # commit under the ENGINE lock too: the LRU's victim.spill()
        # runs under self._lock alone, and a check-then-set of
        # _resident against it could resurrect a just-spilled model's
        # device arrays past the HBM budget. Nesting order is
        # _upload_lock -> engine._lock: a concurrent ensure_resident
        # holding _upload_lock for a seconds-long upload then only
        # delays THIS commit, never the engine lock (and no other path
        # holds engine._lock while waiting on an upload lock, so the
        # nesting cannot deadlock).
        with model._upload_lock:
            with self._lock:
                model.params_host = params_host
                model.state_host = state_host
                if model._resident is not None:
                    # may re-spill (uploaded None: the model became
                    # resident with the OLD weights between the checks)
                    # — stale weights must never serve; the next
                    # ensure_resident uploads the new masters
                    model._resident = uploaded
                    if uploaded is None:
                        model.was_spilled = True
        self.swaps += 1
        log.info("serving: hot-swapped model %r from %s (%s); compiled "
                 "programs untouched", name, weights, source or "manual")
        self._journal("swap", model=name, weights=weights, source=source,
                      swaps=self.swaps)

    def _canary_gate(self, model: InferenceModel, params_host,
                     state_host) -> None:
        """Run the smallest ALREADY-COMPILED bucket with the candidate
        weights on a synthetic batch. Zero compiles by construction;
        raises SwapError on non-finite or wrong-shaped scores (the two
        ways a structurally-loadable weights file can still be poison)."""
        fwd = model.fwd
        b = fwd.ladder[0]
        rng = np.random.RandomState(0)
        batch = rng.rand(b, *fwd.input_shape()[1:]).astype(np.float32)
        try:
            # one deliberate harvest: the canary must SEE the scores
            out = np.asarray(fwd.run_bucket(params_host, state_host,
                                            batch))
        except Exception as e:  # noqa: BLE001 — mismatch => rejection
            raise SwapError(
                f"canary forward failed (params do not fit the "
                f"compiled programs): {e}") from e
        if out.shape[0] != b or out.ndim < 1:
            raise SwapError(
                f"canary scores have wrong shape {out.shape} for "
                f"bucket {b}")
        if not np.all(np.isfinite(out)):
            raise SwapError("canary scores are non-finite")

    def note_swap_rejected(self, name: str, reason: str, *,
                           source: str = "") -> None:
        """Count + journal a rejected hot-swap candidate (corrupt
        snapshot, unloadable weights, failed canary). The previous
        weights keep serving."""
        self.swap_rejections += 1
        log.warning("serving: hot-swap for model %r REJECTED (%s); "
                    "previous weights keep serving", name, reason)
        self._journal("swap_rejected", model=name, swap_reason=reason,
                      source=source, swap_rejections=self.swap_rejections)

    # -- request surface ------------------------------------------------
    def _shed_if_unhealthy(self) -> None:
        """Fast-path health gate shared by every submit surface: an open
        stall breaker sheds in the caller's thread (and kicks a
        background recovery probe) before any decode/preprocess cost."""
        if not self._healthy:
            self._maybe_probe_async()
            self.note_unhealthy_shed()
            raise EngineUnhealthyError(
                "serving engine unhealthy (dispatch stall breaker open"
                f"{'' if not self._breaker else ': ' + str(self._breaker.get('section'))}"
                "); request shed")

    def submit(self, name: str, img: np.ndarray, *, preprocess: bool = True):
        """Enqueue one image; returns a concurrent.futures.Future whose
        result is the model's score row (np.ndarray). Typed failures
        (ISSUE 12): EngineUnhealthyError when the stall breaker is open,
        ShedError when the backlog is at `serve_queue_limit`,
        EngineClosedError after close/drain."""
        self._shed_if_unhealthy()
        model = self.model(name)  # KeyError for unknown models
        data = model.preprocess(img) if preprocess else \
            np.asarray(img, np.float32)
        want = model.fwd.input_shape()[1:]
        if tuple(data.shape) != tuple(want):
            # reject HERE, in the caller's thread: a wrong-shaped row
            # inside a batch would fail every co-batched request
            raise ValueError(
                f"serving: request row shape {tuple(data.shape)} does "
                f"not match model {name!r} input {tuple(want)}")
        return self._batcher.submit(name, data)

    def decode_request(self, data: bytes) -> np.ndarray:
        """Decode one encoded request (HTTP upload bytes) -> (3, h, w)
        planar BGR uint8 through the training decode plane's policy +
        counters and this engine's crc32c-keyed hot-content cache
        (ISSUE 14, serving/ingest.py). Raises the decoder's error for
        non-image bytes — the HTTP front maps it to a typed 400."""
        return self.ingest.decode(data)

    def submit_raw(self, name: str, raw: np.ndarray):
        """Enqueue one DECODED request ((3, h, w) planar BGR uint8, the
        decode plane's pixel contract). When the model's preprocessing
        is expressible in the native fused kernel and the native plane
        is engaged, preprocessing is DEFERRED to the batcher's window
        close — one GIL-released call per dispatch window instead of
        one Python chain per handler thread; otherwise this is exactly
        the classic per-request path (bitwise pre-native behavior,
        including under CAFFE_NATIVE_DECODE=0)."""
        self._shed_if_unhealthy()
        model = self.model(name)  # KeyError for unknown models
        from . import ingest as _ingest
        if _ingest.fused_engaged(model):
            # count AFTER the submit: the batcher may still shed
            # (queue limit) or refuse (closed) — a rejected request
            # must not inflate the engagement counters
            fut = self._batcher.submit(name, raw, raw_mode=True)
            self.ingest._count("deferred_rows")
            return fut
        from ..data.decode import to_float_image
        t0 = time.perf_counter()
        try:
            fut = self.submit(name, to_float_image(raw))
        finally:
            with self.ingest._lock:
                self.ingest.preprocess_s += time.perf_counter() - t0
        self.ingest._count("immediate_rows")
        return fut

    def submit_bytes(self, name: str, data: bytes):
        """decode_request + submit_raw in one call — the library
        spelling of the HTTP upload path. Sheds BEFORE decoding: an
        unhealthy engine must not burn host CPU per rejected upload
        (fast-fail is the breaker's whole point under overload)."""
        self._shed_if_unhealthy()
        return self.submit_raw(name, self.decode_request(data))

    def classify(self, name: str, imgs, *, preprocess: bool = True,
                 timeout: float | None = 600.0) -> np.ndarray:
        """Synchronous convenience: submit all, gather rows in order.
        The gather is deadline-bounded (deadline-discipline): a wedged
        dispatcher behind a hung device call must surface as a TimeoutError
        here, never as an unkillable hang in the caller."""
        futures = [self.submit(name, im, preprocess=preprocess)
                   for im in imgs]
        return np.stack([f.result(timeout=timeout) for f in futures])

    def drain(self, timeout: float = 60.0) -> None:
        self._batcher.drain(timeout)

    # -- telemetry ------------------------------------------------------
    def bank_telemetry(self) -> dict:
        """stats()["bank"]: program-bank counters, cold-start wall time,
        per-model per-bucket warm breakdown (lower/compile/deserialize
        ms, build source), and the netshape plan — per-model footprints
        plus the statically simulated HBM admission in load order."""
        from .plan import plan_admission
        with self._lock:
            plans = {n: dict(p) for n, p in self._plans.items()}
            warm = {n: list(m.fwd.warm_events)
                    for n, m in self._models.items()}
            cold_ms = self.cold_start_ms
        out = {
            "enabled": self.bank is not None,
            "path": self.bank.path if self.bank is not None else "",
            "cold_start_ms": round(cold_ms, 3),
            "warm": warm,
            "plan": {
                "models": plans,
                "admission": plan_admission(
                    [(n, p.get("param_bytes", 0))
                     for n, p in plans.items()], self.hbm_budget),
            },
        }
        out.update(self.bank_stats.snapshot())
        return out

    def stats(self) -> dict:
        """Serving telemetry: p50/p99 end-to-end latency, sustained
        img/s, dispatch fill, and the zero-recompile counters."""
        recs = self._batcher.records()
        out = {
            "requests": len(recs),
            "dispatches": self._batcher.dispatch_count,
            "models": len(self.models),
            "warmed_buckets": self.warmed_buckets,
            "compile_count": self.compile_count,
            "spills": self.spills,
            "reloads": self.reloads,
            "window_ms": self.window_ms,
            # resilience telemetry (ISSUE 12)
            "healthy": self._healthy,
            "stall_trips": self.stall_trips,
            "shed_requests": self._batcher.shed_count,
            "unhealthy_sheds": self.unhealthy_sheds,
            "deadline_failures": self._batcher.deadline_count,
            "queue_limit": self.queue_limit,
            "max_queue_depth": self._batcher.max_queue_depth,
            "deadline_ms": self.deadline_ms,
            "stall_s": self.stall_s,
            "swaps": self.swaps,
            "swap_rejections": self.swap_rejections,
            # request-ingest plane (ISSUE 14): decode-path engagement,
            # window-fused preprocess counters, hot-content cache
            "ingest": self.ingest.stats(),
            # program bank + static plan (ISSUE 17): hit/miss/verify
            # counters, per-bucket warm breakdown, netshape admission
            "bank": self.bank_telemetry(),
        }
        if recs:
            lat = np.sort(np.array([r["total_ms"] for r in recs]))
            qms = np.array([r["queue_ms"] for r in recs])
            first = min(r["t_enqueue"] for r in recs)
            last = max(r["t_done"] for r in recs)
            fills = [n / b
                     for (_, n, b) in self._batcher.dispatch_snapshot()]
            out.update({
                "p50_ms": round(float(np.percentile(lat, 50)), 3),
                "p99_ms": round(float(np.percentile(lat, 99)), 3),
                "mean_queue_ms": round(float(qms.mean()), 3),
                "img_per_s": round(len(recs) / max(last - first, 1e-9), 1),
                "mean_bucket_fill": round(float(np.mean(fills)), 3),
            })
        return out

    def shutdown(self, timeout: float = 60.0) -> None:
        """Graceful drain (ISSUE 12): stop accepting (submits fail with
        EngineClosedError), flush the open batching window immediately,
        resolve every in-flight future, then close. The impatient path
        (`close()`) cancels pending work instead."""
        self._mark_closed()
        self._journal("serve_shutdown", swaps=self.swaps,
                      stall_trips=self.stall_trips)
        self._batcher.shutdown(timeout)
        self._stop_breaker()

    def close(self) -> None:
        self._mark_closed()
        self._batcher.close()
        self._stop_breaker()

    def _mark_closed(self) -> None:
        """Publish _closed under _probe_lock: probe_recovery holds that
        lock across its whole body, so either the probe commits (and
        journals serve_recovered) strictly BEFORE close proceeds, or it
        observes _closed and refuses — never a recovered-after-shutdown
        journal or a healthy /healthz on a closed engine."""
        with self._probe_lock:
            self._closed = True

    def _stop_breaker(self) -> None:
        """Retire the watchdog monitor thread with the engine — an
        embedding app cycling engines must not accumulate pollers.
        Serialized against probe_recovery's re-arm via _probe_lock: a
        close() racing a recovery probe must not leave the freshly
        re-armed watchdog's monitor thread running forever."""
        with self._probe_lock:
            wd = self._watchdog
            self._watchdog = None
        if wd is not None:
            wd.stop()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
