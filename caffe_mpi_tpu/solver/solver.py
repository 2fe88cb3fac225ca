"""Solver — training driver. Functional replacement for reference
src/caffe/solver.cpp + solvers/*.

The reference Solver couples the iteration loop with a reduce thread,
per-param fused update kernels, and NCCL callbacks (solver.cpp:187-351).
Here one jit-compiled `train_step` contains the entire iteration — forward,
backward, (optional) gradient allreduce, LR/momentum schedule, and optimizer
update — so XLA schedules compute/communication overlap that the reference
builds manually with threads and buckets.

Faithful behavior: iter_size gradient accumulation (solver.cpp:277-288),
global_grad_scale loss scaling (net.cpp:116-119,815-818), L2-norm gradient
clipping (sgd_solver.cpp:110-128), smoothed-loss display (solver.cpp:606-617),
img/sec perf report (solver.cpp:619-628), test-interval evaluation with score
averaging (solver.cpp:439-540), snapshot/restore of weights + solver state
(solver.cpp:542-604).
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import threading
import time
from collections import deque
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..net import Net
from ..parallel.mesh import needs_collective_gather
from ..proto.config import NetParameter, NetState, SolverParameter, solver_type
from ..proto.text_format import parse_file
from ..utils import resilience, spans
from ..utils.resilience import FAULTS
from . import lr_policy
from .updates import UPDATE_FNS, Hyper, n_slots

log = logging.getLogger("caffe_mpi_tpu.solver")

FeedFn = Callable[[int], dict]

# dynamic loss-scale schedule (ISSUE 9): torch.amp GradScaler-shaped —
# start high, halve on an overflow (skipped) step, double again after
# `loss_scale_window` consecutive clean steps, clamped to [min, max].
# The floor matters for the divergence policy: overflow skips only count
# toward guard_max_skips once the scale can no longer back off, so a
# recoverable overflow burst rescales instead of exiting 88.
_LS_INIT = 2.0 ** 15
_LS_MIN = 1.0
_LS_MAX = 2.0 ** 24
_LS_BACKOFF = 0.5
_LS_GROWTH = 2.0


def _load_net_param(sp: SolverParameter, phase: str, model_dir: str = "",
                    test_idx: int = 0) -> NetParameter:
    """Resolve the net definition the way reference Solver::Init* does
    (solver.cpp:41-105): inline net_param / net file / train_net / test_net."""
    if phase == "TRAIN":
        if sp.train_net_param is not None:
            return sp.train_net_param
        if sp.train_net:
            return NetParameter.from_file(os.path.join(model_dir, sp.train_net))
    else:
        if sp.test_net_param:
            return sp.test_net_param[test_idx]
        if sp.test_net:
            return NetParameter.from_file(os.path.join(model_dir, sp.test_net[test_idx]))
    if sp.net_param is not None:
        return sp.net_param
    if sp.net:
        return NetParameter.from_file(os.path.join(model_dir, sp.net))
    raise ValueError("solver specifies no net")


@contextlib.contextmanager
def _nested(outer, inner):
    with outer, inner:
        yield


class Solver:
    def __init__(self, sp: SolverParameter, *, model_dir: str = "",
                 batch_divisor: int = 1, grad_transform=None,
                 data_shape_probe=None, rank: int = 0, mesh=None,
                 param_shardings=None, gpipe=None):
        """grad_transform: hook applied to the grad pytree inside the jitted
        step — a custom distributed layer can pass lambda g: psum(g)/n here,
        playing the role of the reference's P2PSync::allreduce callback.

        mesh: a parallel.MeshPlan. When set, training runs SPMD over the
        mesh: params/opt state replicated, feed batches sharded over the
        'data' axis, XLA inserting and overlapping the gradient all-reduce
        (the whole reference parallel.cpp machinery).

        param_shardings: optional {layer_name: spec} tensor-parallel rules
        (see MeshPlan.param_sharding_rules) — sharded layers' weights live
        split over the 'model' axis and GSPMD partitions their matmuls.

        gpipe: heterogeneous MPMD pipeline training (parallel/gpipe.py) —
        an int stage count, or {"stages": S, "micro": M, "devices": [...],
        "boundaries": [...]}. The net is cut into S stages, each pinned to
        its own device; every iteration the global batch splits into M
        microbatches (default S) wavefront-scheduled GPipe-style, and the
        optimizer update runs PER STAGE on that stage's device over the
        params it owns (no cross-device gather in the train loop). The
        reference wires its parallelism into the train entrypoint the same
        way (tools/caffe.cpp:223-225 hands the solver to P2PManager::Run);
        mutually exclusive with mesh/zero_stage, and iter_size must be 1
        (microbatches already carry the accumulation semantics)."""
        with spans.phase("solver/build"):
            self._build(sp, model_dir, batch_divisor, grad_transform,
                        data_shape_probe, rank, mesh, param_shardings, gpipe)

    def _build(self, sp, model_dir, batch_divisor, grad_transform,
               data_shape_probe, rank, mesh, param_shardings, gpipe) -> None:
        self.sp = sp
        self.type = solver_type(sp)
        if self.type not in UPDATE_FNS:
            raise ValueError(f"unknown solver type {self.type!r}")
        self.update_fn = UPDATE_FNS[self.type]
        self.rank = rank

        # mixed-precision bf16 training (ISSUE 9, docs/benchmarks.md
        # "Mixed-precision bf16 training"): "f32" (default) leaves every
        # traced program bitwise-identical to a solver that predates the
        # knob; "bf16" computes activations/gradients in bfloat16 with
        # f32 MASTER params and momentum (updates in f32), and arms loss
        # scaling — static (loss_scale > 0) folds into the existing
        # global_grad_scale plumbing, dynamic (loss_scale 0) rides the
        # guard carry (see _iteration_fn).
        prec = str(getattr(sp, "precision", "") or "f32").lower()
        if prec not in ("f32", "bf16"):
            raise ValueError(
                f"unknown precision {sp.precision!r} (expected 'f32' or "
                "'bf16')")
        self._precision = prec
        ls = float(getattr(sp, "loss_scale", 0.0) or 0.0)
        if ls < 0:
            raise ValueError(
                f"loss_scale must be >= 0 (0 = dynamic), got {ls}")
        lsw = int(getattr(sp, "loss_scale_window", 0) or 0)
        if lsw <= 0 and sp.has("loss_scale_window"):
            raise ValueError(
                f"loss_scale_window must be >= 1, got {lsw}")
        self._ls_window = lsw if lsw > 0 else 200
        # dynamic scaling is a bf16 mechanism: bf16 keeps f32's exponent
        # range, but the SCALED f32 loss/cotangents can still overflow,
        # and the skip+rescale loop is the torch-amp recovery contract
        self._dyn_scale = prec == "bf16" and ls == 0
        self._static_scale = ls if (prec == "bf16" and ls > 0) else 1.0
        if prec == "bf16" and gpipe:
            raise ValueError(
                "precision: bf16 is unsupported under gpipe (stage-local "
                "updates bypass the loss-scaling carry); use the mesh "
                "path")

        self.model_dir = model_dir
        # gpipe micro-batching follows the reference's divide_batch
        # semantics (parallel.cpp:295-348): the prototxt batch is the
        # GLOBAL per-iteration batch; the net is built at batch/M and the
        # feed_fn is consulted M times per iteration (iter_size-style).
        self._gpipe_cfg = None
        self._gpipe_micro = 0
        if gpipe:
            cfg = {"stages": gpipe} if isinstance(gpipe, int) else dict(gpipe)
            n_st = cfg.get("stages") or (len(cfg.get("boundaries") or []) - 1)
            if not n_st or n_st < 1:
                raise ValueError("gpipe needs stages >= 1 (or boundaries)")
            self._gpipe_micro = int(cfg.get("micro") or 0) or int(n_st)
            self._gpipe_cfg = cfg
            batch_divisor = batch_divisor * self._gpipe_micro
        train_param = _load_net_param(sp, "TRAIN", model_dir)
        # train_state/test_state: extra stage/level selectors
        # (reference solver.cpp:41-105 merges them into the NetState)
        tstate = sp.train_state
        self._net_ctor = dict(
            batch_divisor=batch_divisor, data_shape_probe=data_shape_probe,
            model_dir=model_dir, level=tstate.level if tstate else 0,
            stages=tuple(tstate.stage) if tstate else (),
            solver_storage=sp.solver_data_type, precision=self._precision)
        self.net = Net(train_param, phase="TRAIN", **self._net_ctor)
        self.test_nets: list[Net] = []
        n_tests = max(len(sp.test_net), len(sp.test_net_param),
                      1 if (sp.net or sp.net_param is not None) and sp.test_iter else 0)
        for i in range(n_tests):
            tp = _load_net_param(sp, "TEST", model_dir, i)
            ts = sp.test_state[i] if i < len(sp.test_state) else None
            self.test_nets.append(Net(tp, phase="TEST", model_dir=model_dir,
                                      data_shape_probe=data_shape_probe,
                                      level=ts.level if ts else 0,
                                      stages=tuple(ts.stage) if ts else (),
                                      precision=self._precision))

        seed = sp.random_seed if sp.random_seed >= 0 else 0
        self.base_rng = jax.random.PRNGKey(seed)
        self.params, self.net_state = self.net.init(self.base_rng)
        with spans.phase("solver/opt state"):
            self.opt_state = self._init_opt_state()
        self.mesh = mesh
        if param_shardings is None and mesh is not None:
            param_shardings = self._prototxt_shardings() or None
        self._param_shardings = param_shardings
        if param_shardings and mesh is None:
            raise ValueError("param_shardings requires a mesh")
        # ZeRO-1 (TPU extension, proto zero_stage): optimizer slots live
        # sharded over the 'data' axis; the update computes on 1/N of
        # each param and the result all-gathers. {(layer,param): sharding}
        # for every slot actually sharded — consulted inside the step.
        zero = int(getattr(sp, "zero_stage", 0) or 0)
        if zero not in (0, 1):
            raise ValueError(f"zero_stage {zero} unsupported (0 or 1)")
        if zero and mesh is None:
            raise ValueError("zero_stage: 1 requires a device mesh "
                             "(-gpu all or -mesh data=N)")
        self._zero = zero
        self._zero_shardings: dict[tuple, object] = {}
        if param_shardings:
            unknown = set(param_shardings) - set(self.params)
            if unknown:
                raise ValueError(
                    f"param_shardings for unknown layers: {sorted(unknown)}")
        self.gpipe = None
        if mesh is not None:
            # startup weight broadcast (reference parallel.cpp:208-227) —
            # replicated by default, or tensor-parallel-sharded per rules
            with spans.phase("solver/place"):
                self.net_state = mesh.replicate(self.net_state)
                self._place_params_opt()
            self.net.bind_mesh(mesh)
            for tnet in self.test_nets:
                tnet.bind_mesh(mesh)
        if self._gpipe_cfg is not None:
            if mesh is not None:
                raise ValueError("gpipe and mesh are mutually exclusive "
                                 "(pipeline stages own whole devices)")
            if zero:
                raise ValueError("zero_stage with gpipe is unsupported")
            if max(sp.iter_size, 1) > 1:
                raise ValueError(
                    "iter_size > 1 under gpipe is redundant: micro_batches "
                    "already accumulate with iter_size semantics")
            if grad_transform is not None:
                raise ValueError("grad_transform hooks into the SPMD step; "
                                 "unsupported under gpipe")
            cfg = self._gpipe_cfg
            from ..parallel.gpipe import GPipe
            self.gpipe = GPipe(self.net, cfg.get("stages"),
                               boundaries=cfg.get("boundaries"),
                               devices=cfg.get("devices"))
            self._gpipe_update = None  # single jit, built lazily
            # static stage->owned-param-layers partition (ownership never
            # changes after placement; don't rescan every iteration)
            self._gpipe_owned = [
                self.gpipe.owned_param_layers(s, self.params)
                for s in range(self.gpipe.n_stages)]
            with spans.phase("solver/place"):
                self._place_params_opt()
        # overlapped bucketed gradient reduction (ISSUE 6,
        # parallel/reduction.py — reference ReduceAndUpdate,
        # net.cpp:757-913): knob validation always runs (an explicit
        # 0/negative bucket count must fail loudly, not be silently
        # accepted-and-ignored as before); the plan itself is built only
        # when reduce_overlap opts in AND the net/mesh support the
        # per-device backward — otherwise fall back to the implicit
        # GSPMD reduction with the reason logged + queryable
        # (reduction_stats).
        self._reduction = None
        self._reduction_net = None
        self._reduction_fallback: str | None = None
        self._init_reduction(train_param)
        self.iter = 0
        # nets with host-callback layers (DetectNetTransformation) re-enter
        # Python from inside the compiled step; on the CPU backend (whose
        # execution slots are scarce) the driver must wait for each such
        # program before dispatching more work, or the executor deadlocks
        # against the GIL (see layers/detection.py). On TPU the callback
        # runs host-side while the chip computes — no sync, so dispatch
        # stays asynchronous.
        def _has_cb(net):
            return any(getattr(l, "host_callback", False) for l in net.layers)
        on_cpu = jax.default_backend() == "cpu"
        self._sync_steps = on_cpu and _has_cb(self.net)
        self._sync_test = on_cpu and any(map(_has_cb, self.test_nets))
        self._loss_window = deque(maxlen=max(sp.average_loss, 1))
        self._step_jit = None
        self._gpipe_clip_scale = None
        # host-dispatch telemetry: dispatch_count = train-step program
        # launches (one an iteration; each costs host time the device may
        # sit idle for); host_sync_count = display-boundary host
        # materializations (one per display line; the smoothed-loss and
        # rate float()s block on the same iteration). The benchmark reads
        # both deltas over its timed window (benchmarks/layer_metrics/
        # dispatches_per_100_iters.py, host_syncs_per_100_iters.py).
        self.dispatch_count = 0
        self.host_sync_count = 0
        # evaluation telemetry (ISSUE 2): test_dispatch_count = eval
        # program launches (the shared-param copy + one fused scan per
        # T-batch chunk; the classic fallback counts one per batch);
        # test_pass_count = test nets evaluated; eval_stall_ms = host
        # time the TRAIN loop lost to evaluation (boundary dispatch +
        # harvest wait), the number the async pipeline exists to bound
        # (tests/test_fused_eval.py; tools/e2e_lmdb_train.py prints it).
        self.test_dispatch_count = 0
        self.test_pass_count = 0
        self.eval_stall_ms = 0.0
        self._test_fwd_jits: dict[int, Callable] = {}
        self._test_eval_jits: dict[int, Callable] = {}
        # static per-test-net properties (output blobs, shared-param
        # layer names) — computed once, not rebuilt every pass
        self._test_meta: dict[int, tuple] = {}
        self._test_feed_queues: dict[int, object] = {}
        self._pending_eval = None
        self._warned_unsharded_test = False
        # survivable-training state (ISSUE 3): the dispatch watchdog is
        # armed lazily at the first step() when sp.watchdog_deadline > 0;
        # _last_snapshot tracks the newest snapshot THIS run wrote (the
        # run-manifest journal's resume pointer); _snapshot_error carries
        # a failed async writer's (iteration, exception) to the next
        # wait_snapshots() so a silent half-checkpoint can't pass as
        # success.
        self._watchdog = None
        self._heartbeat = None  # ISSUE 11: cross-host loss detection
        # ISSUE 19: degraded-mode grow-back trigger state — primed
        # lazily at the first snapshot boundary of a generation that
        # is missing hosts (see _maybe_admit_rejoin); False = nothing
        # to admit in this generation (full house / no min_hosts)
        self._rejoin = None
        self._last_snapshot: tuple[int, str] | None = None
        self._snapshot_error: tuple[int, BaseException] | None = None
        # self-healing state (ISSUE 4): the on-device non-finite guard.
        # _gstate is the guard carry (skip counter, consecutive-skip
        # counter, longest-burst-this-dispatch, last-bad-iteration,
        # loss EMA) — five device scalars threaded through the train step
        # when train_guard is on; _guard_prev defers the host-side
        # divergence check by one dispatch so the async pipeline never
        # blocks on the iteration it just launched. skipped_steps /
        # guard_sync_count are host counters the benchmark reads
        # (benchmarks/drivers/train.py: skipped steps count as `failed`).
        # dynamic loss scaling (ISSUE 9) reuses the guard machinery: the
        # skip-step select is how an overflowed step is discarded, and
        # the scale/clean-window counters ride the same carry — so a
        # bf16 run with loss_scale 0 arms the guard even when the
        # prototxt never asked for train_guard (there is no bitwise
        # claim to protect on the bf16 path)
        self._guard_on = bool(getattr(sp, "train_guard", False)) \
            or self._dyn_scale
        if self._guard_on and self._gpipe_cfg is not None:
            raise ValueError(
                "train_guard is unsupported under gpipe (the guard "
                "select lives inside the SPMD step; pipeline stages "
                "update per-device)")
        self._gstate = None
        self._guard_prev: tuple[int, dict] | None = None
        self._guard_unchecked = 0
        self.skipped_steps = 0
        self.guard_sync_count = 0
        # ISSUE 9 telemetry (host mirrors of the carried scale state,
        # refreshed at guard checks): overflow_steps counts skipped
        # steps attributed to loss-scale overflow; loss_scale_value is
        # the last materialized dynamic scale (or the static one)
        self.overflow_steps = 0
        self.loss_scale_value = (_LS_INIT if self._dyn_scale
                                 else float(self._static_scale))
        self._grad_transform = grad_transform
        # decls (lr_mult/decay_mult per param) in pytree-congruent form
        self._decls = {
            ln: {pn: d for (l2, pn, d) in self.net.learnable_param_decls()
                 if l2 == ln}
            for ln in {l for (l, _, _) in self.net.learnable_param_decls()}
        }

    def _prototxt_shardings(self) -> dict:
        """Collect per-layer `param_sharding` declarations from the net
        prototxt (the TPU extension making tensor parallelism a model
        property, launchable from one `caffe train -mesh ...` line).
        "rows" = output dim over 'model' (Megatron column-parallel);
        "cols" = input dim over 'model' (row-parallel; GSPMD inserts the
        partial-sum all-reduce)."""
        rules = {}
        for layer in self.net.layers:
            if (layer.lp.type == "Pipeline"
                    and layer.n_stages == self.mesh.mesh.shape.get("model", 1)
                    and layer.n_stages > 1):
                # stacked stage params shard their leading (stage) dim over
                # 'model' automatically: one stage per device is the whole
                # point of PP (parallel/pipeline.py)
                rules[layer.name] = {pn: ("model",) for pn in layer.params}
                continue
            s = getattr(layer.lp, "param_sharding", "")
            if not s:
                continue
            if s == "rows":
                rules[layer.name] = "rows"
            elif s == "cols":
                rules[layer.name] = (None, "model")
            else:
                raise ValueError(
                    f"layer {layer.name!r}: unknown param_sharding {s!r} "
                    "(expected 'rows' or 'cols')")
        return rules

    def _place_params_opt(self) -> None:
        """(Re)apply mesh/gpipe placement to params + optimizer slots —
        used at init and after restore/load_weights so TP shardings (and
        stage placements) survive a checkpoint round-trip."""
        if self.gpipe is not None:
            # stage-partitioned model memory: each layer's params AND its
            # optimizer slots live on the owning stage's device, so the
            # per-stage update runs without any cross-device traffic
            gp = self.gpipe
            self.params = gp.place_params(self.params)
            self.opt_state = {
                ln: {pn: tuple(
                    jax.device_put(s, gp.devices[gp.owner_stage(ln)])
                    for s in slots)
                    for pn, slots in lo.items()}
                for ln, lo in self.opt_state.items()}
            return
        mesh = self.mesh
        if mesh is None:
            return
        if self._param_shardings:
            self.params = mesh.param_sharding_rules(self._param_shardings)(
                self.params)
            self.opt_state = {
                ln: {pn: tuple(
                    jax.device_put(s, self.params[ln][pn].sharding)
                    for s in slots)
                    for pn, slots in lo.items()}
                for ln, lo in self.opt_state.items()}
        else:
            self.params = mesh.replicate(self.params)
            self.opt_state = mesh.replicate(self.opt_state)
        if self._zero:
            # ZeRO-1: re-place slots of replicated params split over
            # 'data'. TP-sharded params keep their slots param-aligned
            # (already partitioned over 'model').
            self._zero_shardings = {}
            tp_layers = set(self._param_shardings or ())
            new_opt = {}
            for ln, lo in self.opt_state.items():
                new_opt[ln] = {}
                for pn, slots in lo.items():
                    zsh = (None if ln in tp_layers else
                           mesh.zero_slot_sharding(
                               self.params[ln][pn].shape))
                    if zsh is None:
                        new_opt[ln][pn] = slots
                    else:
                        self._zero_shardings[(ln, pn)] = zsh
                        new_opt[ln][pn] = tuple(
                            jax.device_put(s, zsh) for s in slots)
            self.opt_state = new_opt

    # ------------------------------------------------------------------
    def _init_reduction(self, train_param) -> None:
        """Validate the reduction knobs and, when `reduce_overlap` opts
        in, build the bucket plan (ISSUE 6). Config errors (0/negative
        bucket count or byte budget, both sizing modes at once,
        overlap without a mesh) raise; NET-shape incompatibilities
        (BatchNorm, MoE, host-callback, data-dependent loss
        normalization, tensor/model parallelism, ZeRO) log a warning
        and fall back to the implicit GSPMD reduction — the
        default/fallback contract."""
        from ..parallel import reduction
        sp = self.sp
        if train_param.has("reduce_buckets") \
                and train_param.reduce_buckets <= 0:
            raise ValueError(
                f"net reduce_buckets must be >= 1, got "
                f"{train_param.reduce_buckets}")
        if sp.reduce_buckets < 0 or (
                sp.has("reduce_buckets") and sp.reduce_buckets == 0):
            raise ValueError(
                f"solver reduce_buckets must be >= 1, got "
                f"{sp.reduce_buckets}")
        if sp.grad_bucket_mb < 0 or (
                sp.has("grad_bucket_mb") and sp.grad_bucket_mb == 0):
            raise ValueError(
                f"grad_bucket_mb must be a positive MiB budget, got "
                f"{sp.grad_bucket_mb}")
        n_buckets = int(getattr(sp, "reduce_buckets", 0) or 0)
        bucket_mb = float(getattr(sp, "grad_bucket_mb", 0.0) or 0.0)
        if n_buckets > 0 and bucket_mb > 0:
            raise ValueError(
                "set either reduce_buckets (bucket count) or "
                "grad_bucket_mb (byte budget), not both")
        if not getattr(sp, "reduce_overlap", False):
            return
        if self.gpipe is not None or self._gpipe_cfg is not None:
            raise ValueError("reduce_overlap is a data-parallel mesh "
                             "feature; unsupported under gpipe")
        if self.mesh is None:
            raise ValueError(
                "reduce_overlap requires a device mesh (-gpu all or "
                "-mesh data=N)")
        fallback = None
        if self.mesh.n_data == 1:
            # the reference's reduce thread is idle at solver_count 1
            # (net.cpp:757-913 never fires); mirroring that keeps the
            # blanket bitwise guarantee — at n=1 the implicit program
            # has no all-reduce for clip/guard fusion to break against
            fallback = ("'data' axis has a single device — nothing to "
                        "reduce (the implicit program is already "
                        "collective-free)")
        elif self.mesh.mesh.shape.get("model", 1) > 1 or \
                self._param_shardings:
            fallback = ("tensor/model parallelism is active; the "
                        "bucketed step is data-parallel only")
        elif self._zero:
            fallback = ("zero_stage 1 reduces via reduce-scatter; "
                        "explicit bucket psums would defeat it")
        else:
            fallback = reduction.unsupported_reason(self.net)
        n_data = self.mesh.n_data
        if fallback is None:
            # the shard_map body runs the net on its LOCAL batch shard:
            # build a shadow net at batch/n — the reference's own
            # divide_batch_size semantics (parallel.cpp:295-348). Param
            # shapes are batch-independent, so the global net's params
            # apply unchanged; a net whose graph hard-codes the global
            # batch (explicit Reshape dims, indivisible batch) fails
            # here and falls back.
            try:
                kw = dict(self._net_ctor)
                kw["batch_divisor"] = kw["batch_divisor"] * n_data
                self._reduction_net = Net(train_param, phase="TRAIN", **kw)
            # lint: ok(typed-failure) — the typed outcome is the logged
            # fallback reason (reduction stats surface it); training
            # continues correct on the implicit GSPMD path
            except Exception as e:
                self._reduction_net = None
                fallback = (f"net does not divide into {n_data} "
                            f"per-device shards: {e}")
        if fallback is not None:
            self._reduction_fallback = fallback
            log.warning("reduce_overlap: falling back to the implicit "
                        "GSPMD reduction — %s", fallback)
            return
        if n_data & (n_data - 1):
            log.warning(
                "reduce_overlap: 'data' axis size %d is not a power of "
                "two; the post-reduce 1/n scale is inexact and the "
                "bucketed step matches the implicit one only to ~1 ulp",
                n_data)
        if not n_buckets and not bucket_mb:
            n_buckets = train_param.reduce_buckets
        self._reduction = reduction.plan_for_net(
            self.net, self.params, n_buckets=n_buckets,
            bucket_bytes=int(bucket_mb * (1 << 20)), n_data=n_data,
            # ISSUE 9: under precision bf16 the buckets pack and psum in
            # bf16 — collective bytes halve; the post-psum 1/n scale and
            # everything downstream run in f32
            wire_dtype="bfloat16" if self._precision == "bf16" else None)
        if self.rank == 0:
            log.info(
                "overlapped bucketed reduction: %d bucket(s) over "
                "'data'=%d, bytes per bucket %s%s",
                len(self._reduction.buckets), n_data,
                list(self._reduction.bucket_bytes),
                " (bf16 wire)" if self._precision == "bf16" else "")

    def reduction_stats(self) -> dict | None:
        """Gradient-reduction telemetry (tests/test_reduction.py, the
        MULTICHIP dryrun in __graft_entry__.py): the active bucket plan
        (mode 'bucketed'), or mode 'implicit' with the fallback reason
        when reduce_overlap could not engage. None when training has no
        mesh (nothing to reduce)."""
        out = None
        if self._reduction is not None:
            out = self._reduction.stats()
        elif self.mesh is not None:
            out = {"mode": "implicit", "n_data": self.mesh.n_data}
            if self._reduction_fallback:
                out["fallback_reason"] = self._reduction_fallback
        if out is not None:
            # ISSUE 11: in a multi-host run the mesh 'data' axis spans
            # processes, so every per-bucket psum is a CROSS-HOST (DCN)
            # collective — the reference's global NCCL communicator
            # (parallel.cpp:166-169) at host granularity
            hosts = jax.process_count()
            out["hosts"] = hosts
            out["cross_host_collectives_per_step"] = (
                out.get("collectives_per_step", 0) if hosts > 1 else 0)
            # ISSUE 19: a generation-managed run (min_hosts) reports
            # WHICH hosts this generation spans, alongside the
            # collective counts
            from ..parallel.mesh import cluster_generation
            gen = cluster_generation()
            if gen is not None:
                out["generation"] = gen["generation"]
                out["generation_hosts"] = gen["hosts"]
                out["world_full"] = gen["world_full"]
        return out

    def step_hlo_text(self, feeds: dict) -> str:
        """Optimized HLO of the single-iteration jitted step for one
        feed dict — the measurement surface for
        reduction.collective_stats (per-step collective counts) and
        for chip_smoke.py's Mosaic/all-reduce checks. Compiles but never
        executes; per-call cost is one XLA compile."""
        args = [self.params, self.net_state, self.opt_state,
                self._place_feeds([feeds] * max(self.sp.iter_size, 1)),
                np.int32(self.iter), self.base_rng]
        if self._guard_on:
            if self._gstate is None:
                self._gstate = self._guard_state0()
            args.append(self._gstate)
        return self._build_step().lower(*args).compile().as_text()

    def _place_feeds(self, micro_feeds: list):
        """The plain step's feed argument from the iteration's iter_size
        feed trees. At iter_size 1 the tree goes in as `feed_fn` returned
        it, leaves (B, ...): `jnp.asarray` is the transfer of a host
        array and hands a device array back untouched, so nothing is
        launched on a batch already on the device. Above 1 the trees are
        stacked on a leading axis for the step's scan."""
        if len(micro_feeds) == 1:
            feeds, batch_axis = jax.tree.map(jnp.asarray, micro_feeds[0]), 0
        else:
            feeds, batch_axis = jax.tree.map(lambda *xs: jnp.stack(xs),
                                             *micro_feeds), 1
        if self.mesh is not None:
            # global batch sharded over the 'data' mesh axis
            # (divide_batch_size semantics, parallel.cpp:295-348)
            feeds = self.mesh.shard_feeds(feeds, batch_axis=batch_axis)
        return feeds

    # ------------------------------------------------------------------
    def _init_opt_state(self):
        k = n_slots(self.type)
        opt = {}
        for lname, pname, decl in self.net.learnable_param_decls():
            arr = self.params[lname][pname]
            opt.setdefault(lname, {})[pname] = tuple(
                jnp.zeros(arr.shape, jnp.float32) for _ in range(k))
        return opt

    # ------------------------------------------------------------------
    def _iteration_fn(self, plain: bool = False):
        """The pure single-iteration training body
            (params, net_state, opt_state, feeds, it, base_rng)
              -> (params, net_state, opt_state, loss, rate)
        that `_build_step` jits: with `plain` it is everything an
        iteration needs besides the feed, so the host launches that one
        program and nothing else. `base_rng` is the solver's base key,
        folded with `it + 1` inside, and at iter_size 1 the feeds are
        what `feed_fn` returned, leaves (B, ...). The leading axis is
        added only when iter_size > 1, where a scan runs over it: on a
        device array `x[None]` is no view but a program that copies the
        batch.

        The default form takes feeds [iter_size, B, ...] and the
        iteration's key already folded; only benchmarks/aot_check.py and
        tests/test_solver_step_programs.py lower it.

        With `train_guard` on (ISSUE 4) the signature grows a trailing
        guard-carry dict and return: after the update is computed, an
        all-finite reduction over loss + the updated params/opt/BN
        state (plus the optional loss-spike check against the carried
        EMA) selects per step between the freshly computed state and
        the unchanged inputs — a skip-step, decided entirely on
        device. On an accepted step the selects pass the exact
        computed arrays through, so guard-on training on clean data
        stays BITWISE equal to guard-off (tests/test_train_guard.py)."""
        sp = self.sp
        net = self.net
        update_fn = self.update_fn
        if self.type == "RMSProp":
            update_fn = partial(update_fn, rms_decay=sp.rms_decay)
        # static bf16 loss scale (ISSUE 9, loss_scale > 0) folds into the
        # existing global_grad_scale plumbing: loss scaled up before the
        # bf16 backward, grads unwound by the same factor in f32. The
        # f32 path multiplies by exactly 1.0 (python float), so its
        # traced program is unchanged.
        grad_scale = sp.global_grad_scale if sp.global_grad_scale else 1.0
        grad_scale = grad_scale * self._static_scale
        iter_size = max(sp.iter_size, 1)
        grad_transform = self._grad_transform
        guard = self._guard_on
        dyn = self._dyn_scale
        ls_window = self._ls_window
        spike = float(getattr(sp, "guard_loss_spike", 0.0) or 0.0)
        ema_decay = float(getattr(sp, "guard_ema_decay", 0.9) or 0.9)
        reduction_plan = self._reduction
        lnet = self._reduction_net
        mesh = self.mesh
        if reduction_plan is not None:
            from ..parallel import reduction as _reduction

        def make_value_and_grad(eff_scale):
            """Gradient routine for one effective loss scale — plain
            whole-tree value_and_grad (GSPMD inserts and places the
            all-reduces), or — when the bucketed reduction plan is
            active (ISSUE 6) — the shard_map variant that psums each
            reverse-topo bucket explicitly so the TPU scheduler can
            overlap the collectives with remaining backward. Its
            loss_fn closes over the batch/n shadow net
            (divide_batch_size, parallel.cpp:295-348): each device
            differentiates its local shard. Built inside the step body
            because under DYNAMIC loss scaling (ISSUE 9) eff_scale is a
            traced scalar read from the guard carry; on the static/f32
            path it is the same python float as ever, so the traced
            program is identical."""
            def loss_fn(params, net_state, feeds, rng):
                blobs, new_state, loss = net.apply(params, net_state, feeds,
                                                   train=True, rng=rng)
                return loss * eff_scale, (new_state, loss)

            if reduction_plan is not None:
                def local_loss_fn(params, net_state, feeds, rng):
                    blobs, new_state, loss = lnet.apply(
                        params, net_state, feeds, train=True, rng=rng)
                    return loss * eff_scale, (new_state, loss)

                return _reduction.bucketed_value_and_grad(
                    local_loss_fn, mesh, reduction_plan)
            return jax.value_and_grad(loss_fn, has_aux=True)

        def body(params, net_state, opt_state, feeds, it, rng,
                 gstate=None):
            net_state0 = net_state
            # dynamic loss scaling: the scale is part of the guard carry
            # — every micro-batch of this step backwards through the
            # carried scale, and the guard's skip decision below is what
            # discards an overflowed step and backs the scale off
            eff_scale = grad_scale * gstate["scale"] if dyn else grad_scale
            value_and_grad = make_value_and_grad(eff_scale)
            # iter_size accumulation: above 1 the feeds pytree has a
            # leading iter_size dim on every leaf (solver.cpp:277-288)
            def micro(carry, feeds_rng):
                acc, net_state = carry
                feeds, mrng = feeds_rng
                (_, (net_state, loss)), grads = value_and_grad(
                    params, net_state, feeds, mrng)
                acc_g, acc_l = acc
                acc_g = jax.tree.map(jnp.add, acc_g, grads)
                return ((acc_g, acc_l + loss), net_state), None

            zero_g = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                                  params)
            rngs = jax.random.split(rng, iter_size)
            if iter_size == 1:
                (_, (net_state, loss)), grads = value_and_grad(
                    params, net_state, feeds, rngs[0])
                total_loss = loss
            else:
                ((grads, total_loss), net_state), _ = jax.lax.scan(
                    micro, ((zero_g, jnp.float32(0.0)), net_state),
                    (feeds, rngs))
            with jax.named_scope(spans.UPDATE):
                # normalize: 1/(iter_size * loss scale) (SGDSolver::Normalize
                # + net.cpp:815-818 loss-scale unwind) — the unwind happens
                # AFTER the cast to f32, so a dynamically-scaled bf16
                # gradient re-enters master range without double rounding
                denom = iter_size * eff_scale
                grads = jax.tree.map(
                    lambda g: g.astype(jnp.float32) / denom, grads)
                loss_out = total_loss / iter_size

                if grad_transform is not None:
                    grads = grad_transform(grads)

                # gradient clipping by global L2 norm (sgd_solver.cpp:110-128)
                if sp.clip_gradients > 0:
                    gnorm = jnp.sqrt(sum(
                        jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
                    scale = jnp.where(gnorm > sp.clip_gradients,
                                      sp.clip_gradients / gnorm, 1.0)
                    grads = jax.tree.map(lambda g: g * scale, grads)

                # iteration-dependent LR/momentum from the iteration
                # scalar — the whole schedule lives on device, so an
                # lr_policy step boundary compiles nothing new
                rate, mom = lr_policy.schedule(sp, it)
                hyper = Hyper(rate=rate, momentum=mom, momentum2=sp.momentum2,
                              delta=sp.delta, weight_decay=sp.weight_decay,
                              reg_l1=(sp.regularization_type == "L1"),
                              t=it + 1)

                new_params = {}
                new_opt = {}
                zero_sh = self._zero_shardings
                repl = self.mesh.replicated() if zero_sh else None
                for lname, lparams in params.items():
                    new_params[lname] = {}
                    new_opt[lname] = {}
                    for pname, w in lparams.items():
                        decl = self._decls[lname][pname]
                        g = grads[lname][pname]
                        slots = opt_state[lname][pname]
                        if decl.lr_mult == 0.0:
                            new_params[lname][pname] = w
                            new_opt[lname][pname] = slots
                            continue
                        zsh = zero_sh.get((lname, pname))
                        if zsh is not None:
                            # ZeRO-1: pin the gradient to the slot partition
                            # (GSPMD lowers the psum of the batch-sharded
                            # backward into a reduce-scatter), update 1/N of
                            # the param on each device, all-gather the result
                            # back to the replicated param layout.
                            g = jax.lax.with_sharding_constraint(g, zsh)
                        w32 = w.astype(jnp.float32)
                        w2, slots2 = update_fn(w32, g, slots, hyper,
                                               decl.lr_mult, decl.decay_mult)
                        if zsh is not None:
                            w2 = jax.lax.with_sharding_constraint(w2, repl)
                        new_params[lname][pname] = w2.astype(w.dtype)
                        new_opt[lname][pname] = slots2
            if not guard:
                return new_params, net_state, new_opt, loss_out, rate

            # --- on-device skip-step guard (ISSUE 4) ---------------------
            # Two load-bearing choices keep accepted steps BITWISE equal
            # to guard-off on CPU:
            # (1) the check reads the update's OUTPUTS (loss + new
            #     params/momentum/BN state), not the gradients — any
            #     non-finite gradient propagates into the updated state,
            #     so the same class is detected (plus NaN entering
            #     through BN statistics alone);
            # (2) the entire guard — finiteness reductions, spike check,
            #     selects, counter arithmetic — lives inside a
            #     `lax.cond` BRANCH, i.e. a separate HLO computation.
            #     XLA fusion cannot cross computation boundaries, so the
            #     forward/backward/update graph keeps exactly the
            #     consumers it has in guard-off mode (its values feed
            #     the conditional's operand tuple, just as they would
            #     feed the program root) and compiles to identical
            #     arithmetic. In-graph selects/reductions consuming the
            #     outputs directly get FUSED back into the update's
            #     epilogues, re-tiling its reductions and perturbing
            #     low-order bits (~1 ULP) — and
            #     `lax.optimization_barrier` does NOT survive the CPU
            #     pipeline to prevent it.
            # The predicate is traced-but-always-true (`it` is never
            # negative), so no simplification pass can fold the
            # conditional away; the unreachable else-branch is the
            # all-skip passthrough, which also keeps both branches
            # structurally distinct.

            def _apply_guard(op):
                (loss_b, newp, newo, news, oldp, oldo, olds, gs,
                 it_b) = op
                ok_fin = jnp.isfinite(loss_b)
                for leaf in jax.tree.leaves((newp, newo, news)):
                    if hasattr(leaf, "dtype") and jnp.issubdtype(
                            leaf.dtype, jnp.floating):
                        ok_fin = jnp.logical_and(
                            ok_fin, jnp.all(jnp.isfinite(leaf)))
                ok = ok_fin
                if spike > 0:
                    # EMA < 0 = "no accepted loss yet": never spikes. A
                    # NaN loss compares False, so the finite check and
                    # the spike check agree on non-finite steps.
                    # ok_fin stays separate: under dynamic loss scaling
                    # only a NON-FINITE skip is an overflow the scale
                    # schedule should react to — a finite loss spike is
                    # a real anomaly, not a scaling artifact.
                    ok = jnp.logical_and(ok, jnp.where(
                        gs["ema"] >= 0, loss_b <= spike * gs["ema"],
                        True))
                # scalar-predicate `where` passes the computed arrays
                # through untouched on accept and keeps params/momentum/
                # BN state at their inputs on skip. The iteration still
                # advances — feeds and RNG stay aligned with the
                # unguarded schedule.
                keep = lambda n, o: jnp.where(ok, n, o)
                ema = gs["ema"]
                if dyn:
                    # ISSUE 9: an OVERFLOW skip (non-finite) under
                    # dynamic loss scaling is a RECOVERABLE event — the
                    # scale backs off and the run continues — so it only
                    # feeds the guard_max_skips divergence counter once
                    # the scale is already at its floor and can no
                    # longer help. A finite SPIKE skip is a genuine
                    # anomaly (no scale change could have caused it) and
                    # counts immediately, like guard-only mode.
                    overflow = jnp.logical_not(ok_fin)
                    at_floor = gs["scale"] <= _LS_MIN
                    counts = jnp.where(overflow, at_floor, True)
                    consec = jnp.where(
                        ok, 0, jnp.where(counts, gs["consec"] + 1,
                                         0)).astype(jnp.int32)
                else:
                    consec = jnp.where(ok, 0, gs["consec"] + 1).astype(
                        jnp.int32)
                new_gs = {
                    "skips": gs["skips"] + jnp.where(ok, 0, 1).astype(
                        jnp.int32),
                    "consec": consec,
                    # longest consecutive run EVER seen (monotone): a
                    # >=M burst that recovers before the host looks
                    # must still trip the divergence policy. Monotone
                    # is safe because reaching M always exits — there
                    # is no "after" in which a stale maximum could
                    # re-trip — and it lets the host check lazily
                    # (rate-limited) without missing bursts.
                    "max_consec": jnp.maximum(gs["max_consec"], consec),
                    "last_bad": jnp.where(ok, gs["last_bad"],
                                          it_b).astype(jnp.int32),
                    # the EMA absorbs ACCEPTED losses only: a diverging
                    # tail cannot drag the spike baseline up after itself
                    "ema": jnp.where(
                        ok, jnp.where(ema >= 0,
                                      ema_decay * ema
                                      + (1.0 - ema_decay) * loss_b,
                                      loss_b),
                        ema).astype(jnp.float32),
                }
                if dyn:
                    # loss-scale schedule (ISSUE 9): halve on OVERFLOW
                    # (non-finite) skips only — a finite spike skip
                    # leaves the scale alone (halving real gradients
                    # toward underflow would not address it) — and grow
                    # 2x after ls_window consecutive clean steps;
                    # `good` is the clean-step counter, reset by both a
                    # growth event and any skip
                    good = jnp.where(ok, gs["good"] + 1, 0).astype(
                        jnp.int32)
                    grow = jnp.logical_and(ok, good >= ls_window)
                    scale = jnp.where(
                        grow,
                        jnp.minimum(gs["scale"] * _LS_GROWTH, _LS_MAX),
                        jnp.where(overflow,
                                  jnp.maximum(gs["scale"] * _LS_BACKOFF,
                                              _LS_MIN), gs["scale"]))
                    new_gs["scale"] = scale.astype(jnp.float32)
                    new_gs["good"] = jnp.where(grow, 0, good).astype(
                        jnp.int32)
                    new_gs["overflows"] = (
                        gs["overflows"] + jnp.where(overflow, 1,
                                                    0)).astype(jnp.int32)
                return (jax.tree.map(keep, newp, oldp),
                        jax.tree.map(keep, news, olds),
                        jax.tree.map(keep, newo, oldo), new_gs)

            def _all_skip(op):  # unreachable (it >= 0 always)
                (_loss_b, _newp, _newo, _news, oldp, oldo, olds, gs,
                 it_b) = op
                out_gs = {
                    "skips": gs["skips"] + 1,
                    "consec": gs["consec"] + 1,
                    "max_consec": jnp.maximum(gs["max_consec"],
                                              gs["consec"] + 1),
                    "last_bad": it_b,
                    "ema": gs["ema"],
                }
                if dyn:
                    out_gs["scale"] = jnp.maximum(
                        gs["scale"] * _LS_BACKOFF, _LS_MIN).astype(
                            jnp.float32)
                    out_gs["good"] = jnp.int32(0)
                    out_gs["overflows"] = (gs["overflows"] + 1).astype(
                        jnp.int32)
                return (oldp, olds, oldo, out_gs)

            with jax.named_scope(spans.UPDATE):
                new_params, net_state, new_opt, new_gstate = jax.lax.cond(
                    it >= 0, _apply_guard, _all_skip,
                    (loss_out, new_params, new_opt, net_state,
                     params, opt_state, net_state0, gstate, it))
            return (new_params, net_state, new_opt, loss_out, rate,
                    new_gstate)

        if plain:
            def step(params, net_state, opt_state, feeds, it, base_rng,
                     gstate=None):
                rng = jax.random.fold_in(base_rng, it + 1)
                return body(params, net_state, opt_state, feeds, it, rng,
                            gstate)
        elif iter_size == 1:
            # the stacked form, read by benchmarks/aot_check.py
            def step(params, net_state, opt_state, feeds_stack, it, rng,
                     gstate=None):
                feeds = jax.tree.map(lambda x: x[0], feeds_stack)
                return body(params, net_state, opt_state, feeds, it, rng,
                            gstate)
        else:
            step = body
        return step

    def _train_donate_argnums(self) -> tuple[int, ...]:
        """Donate (params, net_state, opt_state) into the train program —
        on accelerators. On the CPU host platform donation is disabled:
        the CPU client of the jax this was written against
        intermittently corrupted donated train state when several
        dispatches were in flight (reproduced ~50% on the
        8-virtual-device client as a resumed `-train_guard` run whose
        replayed weights differ run-to-run; any host sync between
        dispatches — display, per-iteration snapshots — masks it, and
        dropping donation alone eliminated it over dozens of trials).
        Same buffer-handoff hazard family as the async-snapshot SIGABRT
        (see snapshot()), one layer deeper. Not re-tested on jax 0.9.0
        (ROADMAP Design 1e). Donation never changes numerics — only
        buffer reuse — so CPU test runs stay bitwise identical to
        donating builds; on TPU the donation is load-bearing (params +
        momentum would otherwise double their HBM footprint)."""
        if jax.default_backend() == "cpu":
            return ()
        return (0, 1, 2)

    def _build_step(self):
        # the guard carry (5 scalars) is NOT donated: the deferred
        # divergence check reads the previous dispatch's gstate after
        # the next one launches, so its buffer must stay valid
        with spans.phase("solver/jit"):
            return jax.jit(self._iteration_fn(plain=True),
                           donate_argnums=self._train_donate_argnums())

    # ------------------------------------------------------------------
    # GPipe mode: the train step is the MPMD wavefront in
    # parallel/gpipe.py; the optimizer update runs per stage, on the
    # stage's own device, over the params that stage owns — the pipelined
    # analogue of the reference's per-GPU fused update after the reduce
    # (net.cpp:844, sgd_solver.cpp:143-149). One jitted update serves all
    # stages (jax re-specializes per input structure/device).
    def _build_gpipe_update(self):
        sp = self.sp
        update_fn = self.update_fn
        if self.type == "RMSProp":
            update_fn = partial(update_fn, rms_decay=sp.rms_decay)
        decls = self._decls

        @jax.named_scope(spans.UPDATE)
        def upd(params_s, grads_s, opt_s, rate, mom, it, gscale):
            hyper = Hyper(rate=rate, momentum=mom, momentum2=sp.momentum2,
                          delta=sp.delta, weight_decay=sp.weight_decay,
                          reg_l1=(sp.regularization_type == "L1"),
                          t=it + 1)
            new_p, new_o = {}, {}
            for ln, lparams in params_s.items():
                new_p[ln], new_o[ln] = {}, {}
                for pn, w in lparams.items():
                    decl = decls[ln][pn]
                    g = grads_s.get(ln, {}).get(pn)
                    slots = opt_s[ln][pn]
                    if decl.lr_mult == 0.0 or g is None:
                        new_p[ln][pn] = w
                        new_o[ln][pn] = slots
                        continue
                    g = g.astype(jnp.float32) * gscale
                    w32 = w.astype(jnp.float32)
                    w2, slots2 = update_fn(w32, g, slots, hyper,
                                           decl.lr_mult, decl.decay_mult)
                    new_p[ln][pn] = w2.astype(w.dtype)
                    new_o[ln][pn] = slots2
            return new_p, new_o

        return jax.jit(upd, donate_argnums=(0, 2))

    def _gpipe_iteration(self, feed_fn):
        """One pipelined iteration: M net-shaped micro-batch feeds (the net
        was built at prototxt_batch / M — divide_batch semantics), the
        GPipe wavefront, then stage-local updates. Returns (device loss,
        learning rate)."""
        gp, M = self.gpipe, self._gpipe_micro
        micro = [feed_fn(self.iter * M + m) for m in range(M)]
        rng = jax.random.fold_in(self.base_rng, self.iter + 1)
        rngs = list(jax.random.split(rng, M))
        # global_grad_scale: seed the backward scaled (low-precision
        # cotangents must not underflow in the stage vjps), unwind in the
        # per-stage update via gscale (net.cpp:116-119, 815-818)
        lscale = self.sp.global_grad_scale or 1.0
        loss, grads, self.net_state = gp.train_step(
            self.params, self.net_state, micro, rngs=rngs,
            loss_scale=lscale)

        if self._gpipe_update is None:
            self._gpipe_update = self._build_gpipe_update()
            self._gpipe_sqnorm = jax.jit(lambda g: sum(
                jnp.sum(jnp.square(x)).astype(jnp.float32)
                for x in jax.tree.leaves(g)))
        gscale_arr = jnp.float32(1.0 / lscale)  # unwind grad loss scaling
        if self.sp.clip_gradients > 0:
            # the clip norm spans ALL stages: per-stage partial sums stay
            # on their devices, hop to stage 0, and the combined update
            # scale (clip * loss-scale unwind) is computed there as a
            # DEVICE scalar — zero host syncs in the iteration (a
            # float() here would block the host on the device every
            # single iteration; the host only materializes at display
            # intervals). grads are loss-scaled, so the norm unwinds by
            # 1/lscale before the clip comparison.
            parts = []
            for owned in self._gpipe_owned:
                gs = {ln: grads[ln] for ln in owned if ln in grads}
                if gs:
                    parts.append(jax.device_put(self._gpipe_sqnorm(gs),
                                                gp.devices[0]))
            if self._gpipe_clip_scale is None:
                clip = float(self.sp.clip_gradients)

                def clip_scale(sq, lscale=lscale, clip=clip):
                    gnorm = jnp.sqrt(sq) / lscale
                    return jnp.where(gnorm > clip, clip / gnorm,
                                     jnp.float32(1.0)) / lscale
                self._gpipe_clip_scale = jax.jit(clip_scale)
            gscale_arr = self._gpipe_clip_scale(sum(parts))

        it = jnp.int32(self.iter)
        rate = lr_policy.learning_rate(self.sp, it)
        mom = lr_policy.momentum(self.sp, it)
        upd = self._gpipe_update
        for owned, dev in zip(self._gpipe_owned, gp.devices):
            if not owned:
                continue
            p_s = {ln: self.params[ln] for ln in owned}
            g_s = {ln: grads[ln] for ln in owned if ln in grads}
            o_s = {ln: self.opt_state[ln] for ln in owned}
            # the scale lives on stage 0; hand each stage its own async
            # device-to-device copy (committed inputs to one jit must
            # share a device) — still no host round-trip
            new_p, new_o = upd(p_s, g_s, o_s, rate, mom, it,
                               jax.device_put(gscale_arr, dev))
            self.params.update(new_p)
            self.opt_state.update(new_o)
        return loss, rate

    # ------------------------------------------------------------------
    # Survivable training (ISSUE 3, utils/resilience.py): every
    # device-blocking region in the train loop — dispatch, feed wait,
    # display/harvest sync, snapshot gather — runs inside a watchdog
    # `section`. A device call that never returns (a wedged runtime, a
    # lost peer mid-collective) hangs inside C++ where no Python signal
    # can interrupt; the watchdog's monitor
    # thread journals the run state (iteration, last verified snapshot,
    # RNG cursor) to `<prefix>.run.json` and hard-exits with
    # resilience.EXIT_WATCHDOG so the supervisor (`cli train
    # --max-restarts`) can restart from the newest verified snapshot.
    # Off by default (sp.watchdog_deadline == 0): zero change for
    # existing solvers, and _guard() then opens its profiler span alone.

    def _ensure_watchdog(self) -> None:
        if self._watchdog is not None:
            return
        deadline = float(getattr(self.sp, "watchdog_deadline", 0.0) or 0.0)
        # ISSUE 11: the cross-host heartbeat rides the same monitor
        # thread (its pulse hook) — a dead peer mid-collective and a
        # dispatch that never returns are the same failure shape,
        # bounded by the same thread. host_deadline > 0 in a multi-process run
        # arms it; single-host runs never pay for the check.
        host_deadline = float(getattr(self.sp, "host_deadline", 0.0)
                              or 0.0)
        hb = None
        if host_deadline > 0 and jax.process_count() > 1:
            from ..parallel.mesh import heartbeat_transport
            hb = resilience.HostHeartbeat(
                heartbeat_transport(), jax.process_index(),
                jax.process_count(), host_deadline,
                on_lost=self._host_lost_journal)
            log.info("cross-host heartbeat armed: %d host(s), %.1fs "
                     "deadline, %.2fs beat interval (exit %d on a lost "
                     "peer)", jax.process_count(), host_deadline,
                     hb.interval, resilience.EXIT_CLUSTER)
        if deadline <= 0 and hb is None:
            return
        poll = None
        if hb is not None:
            # tick at least twice per beat interval so publishes are
            # never later than peers' expectations
            poll = hb.interval / 2.0
            if deadline > 0:
                poll = min(poll, max(deadline / 4.0, 0.05))
        self._heartbeat = hb
        self._watchdog = resilience.DispatchWatchdog(
            deadline if deadline > 0 else float("inf"),
            self._watchdog_journal, poll=poll,
            pulse=hb.tick if hb is not None else None)
        if deadline > 0:
            log.info("dispatch watchdog armed: %.1fs deadline (journals "
                     "to %s and exits %d on a stuck dispatch)", deadline,
                     resilience.run_manifest_path(
                         self.sp.snapshot_prefix or "snapshot"),
                     resilience.EXIT_WATCHDOG)

    def _guard(self, label: str):
        """The one boundary every blocking host section passes: the
        profiler span `caffe/solver/<label>` (utils/spans.py) and, when
        the watchdog is armed, its section of the same label."""
        span = spans.span("solver/" + label)
        wd = self._watchdog
        return span if wd is None else _nested(span, wd.section(label))

    def _watchdog_journal(self, label: str, elapsed: float) -> None:
        self._journal_run_state(
            f"watchdog:{label}", stalled_s=round(elapsed, 1),
            deadline_s=float(getattr(self.sp, "watchdog_deadline", 0.0)))

    def heartbeat_farewell(self) -> None:
        """Publish the clean-departure beat (ISSUE 11). Call ONLY after
        the end-of-training barrier has succeeded — peers then stop
        expecting beats instead of tripping on shutdown skew. Never
        called on failure paths: a crashed host must stay mournable."""
        if self._heartbeat is not None:
            self._heartbeat.farewell()

    def _host_lost_journal(self, peer: int, elapsed: float) -> None:
        """Heartbeat on_lost callback (ISSUE 11): record WHICH peer went
        silent before the monitor hard-exits 87. Critical — every rank
        journals (non-zero ranks to their own `.r<k>` journal), because
        the host that noticed first is exactly the forensic fact the
        operator needs."""
        self._journal_run_state(
            f"host_lost:{int(peer)}", critical=True, peer=int(peer),
            silent_s=round(elapsed, 1),
            host_deadline_s=float(getattr(self.sp, "host_deadline", 0.0)),
            exit_code=resilience.EXIT_CLUSTER)

    # ------------------------------------------------------------------
    # Self-healing training (ISSUE 4): host side of the on-device guard.

    # the guard counters are checked every Nth dispatch (each check is
    # a device_get = one host sync). Detection latency is bounded by N
    # iterations.
    _GUARD_CHECK_EVERY = 16

    def _guard_state0(self) -> dict:
        """Fresh guard carry: no skips, no consecutive run, no bad
        iteration seen, loss EMA unset (-1 sentinel)."""
        gs = {"skips": jnp.int32(0), "consec": jnp.int32(0),
              "max_consec": jnp.int32(0),
              "last_bad": jnp.int32(-1), "ema": jnp.float32(-1.0)}
        if self._dyn_scale:
            # ISSUE 9: the dynamic loss scale and its clean-step /
            # overflow counters ride the same carry — zero extra
            # dispatches, and the scale-down decision never leaves HBM
            gs["scale"] = jnp.float32(_LS_INIT)
            gs["good"] = jnp.int32(0)
            gs["overflows"] = jnp.int32(0)
        if self.mesh is not None:
            gs = self.mesh.replicate(gs)
        return gs

    def _check_guard(self, boundary_iter: int, gstate) -> None:
        """Materialize the guard counters of the dispatch that ended at
        `boundary_iter` (a rate-limited host read — the only host
        traffic the guard adds) and apply the divergence policy:
        guard_max_skips consecutive skips journals the anomaly to
        `<prefix>.run.json` and raises NumericAnomalyError, which the
        CLI converts to exit code 88 for the supervisor to rewind."""
        if gstate is None:
            return
        with self._guard("guard check"):
            # 5 scalars, one transfer — not per-iteration
            vals = jax.device_get(gstate)
        # max_consec = longest burst seen over the RUN (monotone in the
        # carry; reset only by restore()): a >=M run that recovered
        # before this check still trips the policy, even though
        # `consec` reset on the accepted step that ended it. Monotone
        # is sound because tripping exits the process — a caller that
        # swallowed NumericAnomalyError and kept stepping would re-trip
        # on every later check by design.
        consec = max(int(vals["consec"]), int(vals["max_consec"]))
        skips = int(vals["skips"])
        last_bad = int(vals["last_bad"])
        self.guard_sync_count += 1
        if "scale" in vals:
            # ISSUE 9: dynamic loss-scale telemetry rides the same
            # 5(+3)-scalar transfer — no extra host traffic
            overflows = int(vals["overflows"])
            scale = float(vals["scale"])
            if overflows > self.overflow_steps and self.rank == 0:
                log.warning(
                    "loss scale: %d overflow step(s) so far (+%d since "
                    "the last check), skipped and rescaled — scale now %g",
                    overflows, overflows - self.overflow_steps, scale)
            self.overflow_steps = overflows
            self.loss_scale_value = scale
        if skips > self.skipped_steps and self.rank == 0:
            log.warning(
                "train guard: %d skipped step(s) so far (+%d since the "
                "last check, last bad iteration %d, %d consecutive)", skips,
                skips - self.skipped_steps, last_bad, consec)
        self.skipped_steps = skips
        m = int(getattr(self.sp, "guard_max_skips", 0) or 0)
        if m > 0 and consec >= m:
            extra = {}
            if "scale" in vals:
                # under dynamic scaling this only trips once the scale
                # sat at its floor for m consecutive skips: a genuine
                # divergence, not an overflow the schedule could absorb
                extra = {"loss_scale": float(vals["scale"]),
                         "overflow_steps": int(vals["overflows"])}
            self._journal_run_state(
                "numeric_anomaly", consec_skips=consec,
                skipped_steps=skips, last_bad_iter=last_bad,
                exit_code=resilience.EXIT_NUMERIC, **extra)
            raise resilience.NumericAnomalyError(
                boundary_iter, consec, skips, last_bad)

    def _journal_run_state(self, reason: str, critical: bool = False,
                           **extra) -> None:
        """Write the run manifest: the journal `--resume auto` and the
        operator read after a crash. Best-effort — journaling failures
        must never take down training. Rank 0 owns `<prefix>.run.json`;
        non-zero ranks journal only `critical` cluster events (host
        loss, ISSUE 11) and to their own `<prefix>.r<k>.run.json` — N
        hosts racing atomic rewrites of one shared journal would drop
        each other's last words."""
        if self.rank != 0 and not critical:
            return
        last_it, last_state = self._last_snapshot or (None, None)
        prefix = self.sp.snapshot_prefix or "snapshot"
        if self.rank != 0:
            prefix = f"{prefix}.r{self.rank}"
        try:
            resilience.write_run_manifest(
                prefix, reason=reason, iter=int(self.iter),
                random_seed=int(self.sp.random_seed),
                last_snapshot_iter=last_it,
                last_snapshot_state=last_state, **extra)
        except OSError:
            log.exception("run-manifest journal failed (continuing)")

    def _maybe_admit_rejoin(self) -> None:
        """Degraded-mode grow-back trigger (ISSUE 19, gated on the
        `min_hosts` solver knob — docs/robustness.md "Degraded-mode
        elasticity"). In a generation that is missing hosts, rank 0
        watches the missing hosts' SUPERVISOR beat files (the shared
        `<prefix>.cluster/` directory the elastic supervisor exports
        via CAFFE_TPU_CLUSTER_DIR) at every snapshot boundary: the
        first boundary primes the sequences (a frozen beat file left
        by the dead incarnation must not read as a revival), and a
        later boundary that observes an ADVANCE raises a journaled
        ClusterError with reason `cluster_rejoin` — the worker exits
        87 on the snapshot it just wrote, and the supervisors'
        membership round re-forms the cluster one generation up, with
        the rejoiner re-admitted and every rank resuming from this
        boundary's snapshot. Zero cost when min_hosts is unset."""
        if not getattr(self.sp, "min_hosts", 0) or self.rank != 0:
            return
        if self._rejoin is False:
            return
        if self._rejoin is None:
            cdir = os.environ.get("CAFFE_TPU_CLUSTER_DIR", "")
            hosts_env = os.environ.get("CAFFE_TPU_CLUSTER_HOSTS", "")
            world_full = int(
                os.environ.get("CAFFE_TPU_WORLD_FULL", "0") or 0)
            missing: list[int] = []
            if cdir and hosts_env and world_full:
                present = {int(h) for h in hosts_env.split(",") if h}
                missing = sorted(set(range(world_full)) - present)
            if not (cdir and missing):
                self._rejoin = False
                return
            tr = resilience.DirBeatTransport(os.path.join(cdir, "hb"))
            self._rejoin = (tr, {h: tr.latest_seq(h) for h in missing})
            return
        tr, base = self._rejoin
        back = []
        for h, primed in base.items():
            try:
                if tr.latest_seq(h) > primed:
                    back.append(h)
            except OSError:
                pass
        if not back:
            return
        self._journal_run_state("cluster_rejoin", critical=True,
                                rejoining_hosts=back,
                                boundary_iter=int(self.iter))
        err = resilience.ClusterError(
            f"host(s) {back} beating again at snapshot boundary "
            f"iteration {self.iter}; exiting for the grow-back "
            f"generation")
        err.journal_reason = "cluster_rejoin"
        raise err

    # ------------------------------------------------------------------
    def step(self, n: int, feed_fn: FeedFn, test_feed_fns=None) -> float:
        """Run n training iterations (reference Solver::Step)."""
        if self._step_jit is None:
            self._step_jit = self._build_step()
        self._ensure_watchdog()
        # ISSUE 4 fault sites nan_grad/loss_spike poison feed batches;
        # wrap_feeds returns feed_fn UNCHANGED when neither is configured
        feed_fn = FAULTS.wrap_feeds(feed_fn)
        if self._guard_on and self._gstate is None:
            self._gstate = self._guard_state0()
        sp = self.sp
        iter_size = max(sp.iter_size, 1)
        last_loss = float("nan")
        t0, it0 = time.time(), self.iter
        imgs_per_iter = self._batch_images() * iter_size \
            * max(self._gpipe_micro, 1)
        while n > 0:
            with spans.iteration(self.iter):
                # test-only: simulates "the process died mid-run" for the
                # supervised auto-resume suite (no cost when faults are off)
                FAULTS.maybe_exit("train_abort", key=self.iter)
                if (sp.test_interval and self.iter % sp.test_interval == 0
                        and (self.iter > 0 or sp.test_initialization)
                        and test_feed_fns):
                    # asynchronous evaluation: drain the previous pass (its
                    # scores are certainly computed by now — its programs
                    # preceded a full test_interval of train steps), then
                    # dispatch this one and resume training immediately; the
                    # device runs the eval between train steps
                    self._harvest_eval()
                    with spans.span("solver/eval dispatch"):
                        self._start_eval(test_feed_fns)
                if self.gpipe is not None:
                    with self._guard("train dispatch"):
                        loss, rate = self._gpipe_iteration(feed_fn)
                else:
                    # feed assembly + host->device transfer are watchdog
                    # sections too: a hung runtime blocks inside the
                    # jnp.asarray/shard_feeds C++ transfer exactly like a
                    # dispatch
                    with self._guard("feed wait"):
                        feeds = self._place_feeds(
                            [feed_fn(self.iter * iter_size + k)
                             for k in range(iter_size)])
                    with self._guard("train dispatch"):
                        # the one launch of the iteration: its key is
                        # folded inside the program, and the counter goes
                        # in as a host scalar (4 bytes moved, no program
                        # of its own)
                        it = np.int32(self.iter)
                        FAULTS.maybe_stall("dispatch_stall")
                        if self._guard_on:
                            (self.params, self.net_state, self.opt_state,
                             loss, rate, self._gstate) = self._step_jit(
                                self.params, self.net_state,
                                self.opt_state, feeds, it,
                                self.base_rng, self._gstate)
                        else:
                            (self.params, self.net_state, self.opt_state,
                             loss, rate) = self._step_jit(
                                self.params, self.net_state,
                                self.opt_state, feeds, it,
                                self.base_rng)
                self.dispatch_count += 1
                # feed any in-flight eval pass the chunks whose super-batches
                # the worker finished while this iteration dispatched —
                # non-blocking, so eval assembly never stalls training
                self._continue_eval()
                if self._sync_steps:
                    with self._guard("step sync"):
                        jax.block_until_ready(loss)
                # keep the loss ON DEVICE: a float() here would force a host
                # sync every iteration (the reference pays microseconds over
                # PCIe; over a remote TPU link it would serialize the
                # pipeline). Materialize only at display boundaries.
                last_loss = loss
                self._loss_window.append(loss)
                if (sp.display and self.iter % sp.display == 0
                        and self.rank == 0):
                    with self._guard("display sync"):
                        # one transfer of the window's scalars, summed on
                        # the host: the read launches no program either
                        smoothed = float(sum(  # host-sync: ok (display)
                            jax.device_get(list(self._loss_window)))) / len(
                                self._loss_window)
                    self.host_sync_count += 1
                    elapsed = time.time() - t0
                    done = self.iter - it0 + 1
                    ips = (done * imgs_per_iter / elapsed
                           if elapsed > 0 else 0.0)
                    log.info("Iteration %d (%.4g iter/s, %.1f img/s), "
                             "loss = %.6g, "
                             "lr = %.6g", self.iter,  # host-sync: ok (display)
                             done / max(elapsed, 1e-9), ips,
                             smoothed, float(rate))
                self.iter += 1
                n -= 1
                if self._guard_on:
                    # deferred divergence check: materialize a PREVIOUS
                    # dispatch's guard counters now that this one is in
                    # flight — the read blocks on a program that has almost
                    # certainly retired, so the pipeline stays full. A
                    # per-iteration device_get would cost one host sync per
                    # iteration, so checks rate-limit to every
                    # _GUARD_CHECK_EVERY dispatches — safe, because the
                    # carried counters (skips, consec, monotone max_consec)
                    # lose nothing between checks; only detection latency
                    # is bounded by the interval
                    prev, self._guard_prev = (self._guard_prev,
                                              (self.iter - 1, self._gstate))
                    self._guard_unchecked += 1
                    if (prev is not None and self._guard_unchecked
                            >= self._GUARD_CHECK_EVERY):
                        self._guard_unchecked = 0
                        self._check_guard(*prev)
                if (sp.test_interval and test_feed_fns
                        and self.iter % sp.test_interval == 0
                        and (self.iter > 0 or sp.test_initialization)
                        and (n > 0 or self.iter < sp.max_iter)):
                    # the next loop pass (or next step() call) starts an
                    # eval here: warm its first test super-batch while the
                    # iteration that just dispatched computes. At max_iter no
                    # eval can follow — don't assemble a super-batch nobody
                    # will consume (it would pin HBM until close())
                    self._prefetch_test_feeds(test_feed_fns)
                if sp.snapshot and self.iter % sp.snapshot == 0:
                    if self._guard_on and self._guard_prev is not None:
                        # the snapshot at this boundary becomes the rewind
                        # target: the dispatches since the last check must
                        # pass their divergence check FIRST, or a >=M burst
                        # among them gets sealed into a verified snapshot
                        # that the supervisor then rewinds to — skipping the
                        # divergent region instead of replaying it
                        # (iteration-exactness lost). The extra host read
                        # is snapshot-rate, and snapshot() blocks on this
                        # state moments later anyway.
                        prev, self._guard_prev = self._guard_prev, None
                        self._check_guard(*prev)
                    # interval snapshots don't stall the train loop (the
                    # reference's do: solver.cpp:339-344 writes inline)
                    self.snapshot(block=False)
                    # ISSUE 19: snapshot boundaries are the only points a
                    # degraded cluster may grow back at (the resume target
                    # the re-formed cluster restores is the snapshot just
                    # written). MAIN thread on purpose: the async snapshot
                    # writer swallows raises into _snapshot_error.
                    self._maybe_admit_rejoin()
        if self._guard_on and self._guard_prev is not None:
            # drain the deferred check so a divergence in THIS call's
            # final iterations surfaces before step() returns
            prev, self._guard_prev = self._guard_prev, None
            self._check_guard(*prev)
        # a pass dispatched at the final boundary must land before step()
        # returns (step's contract is "n iterations ran, events fired");
        # by now the eval programs sit ahead of the last train steps in
        # device order, so this wait is dispatch drain, not the pass
        self._harvest_eval()
        return float(last_loss) if last_loss is not None else float("nan")

    def close(self) -> None:
        """Release host-side training resources: joins in-flight async
        snapshots and shuts down the test feed queues' worker threads
        (harmless if no evaluation ran). Long-lived processes that
        construct many Solvers should call this; training results are
        unaffected either way. A failed async snapshot still re-raises
        (wait_snapshots), but worker threads and the watchdog are
        released first — an error exit must not leak a chip-holding
        thread."""
        try:
            self.wait_snapshots()
        finally:
            if self._pending_eval is not None:
                # only reachable via _start_eval without a matching
                # harvest (step()/test_all always drain); don't add a
                # device wait to teardown — a hung device call would
                # turn close() into a hang
                self._pending_eval = None
                log.warning("dropping un-harvested evaluation pass at "
                            "close")
            for q in self._test_feed_queues.values():
                q.close()
            self._test_feed_queues.clear()
            # NOTE: no heartbeat farewell here — close() also runs on
            # FAILURE exits (cmd_train's finally), and a crashing host
            # marked as a clean departure would stop its peers
            # monitoring it forever; the CLI publishes the farewell
            # explicitly after the end-of-training barrier
            # (heartbeat_farewell), the only place departure is clean.
            self._heartbeat = None
            if self._watchdog is not None:
                self._watchdog.stop()
                self._watchdog = None

    def solve(self, feed_fn: FeedFn, test_feed_fns=None) -> float:
        """Train to max_iter (reference Solver::Solve)."""
        loss = self.step(self.sp.max_iter - self.iter, feed_fn, test_feed_fns)
        if self.should_snapshot_after_train():
            self.snapshot()
        self.wait_snapshots()  # async interval writes land before return
        return loss

    def should_snapshot_after_train(self) -> bool:
        """After-train snapshot, unless the interval snapshot just fired
        (reference solver.cpp:402-407)."""
        return bool(self.sp.snapshot_after_train and (
            not self.sp.snapshot or self.iter % self.sp.snapshot != 0))

    def _batch_images(self) -> int:
        for blob in self.net.feed_blobs:
            return self.net.blob_shapes[blob][0]
        return 0

    # ------------------------------------------------------------------
    # Evaluation (reference Solver::TestAll/Test, solver.cpp:439-540) —
    # rebuilt as a fused, device-fed, ASYNCHRONOUS pipeline (ISSUE 2).
    # The pre-ISSUE-2 shape was a host loop of one jitted forward per
    # test batch: test_iter dispatches, each a host round-trip, with
    # training stalled for the whole pass. Now one jitted `lax.scan`
    # consumes a [T, B, ...] test super-batch and carries the per-blob
    # sum accumulators in HBM — ceil(test_iter/T) dispatches per pass —
    # fed by DeviceFeedQueue double-buffering, and because the
    # accumulator is the scan carry AND the
    # program's acc0 input, chunks chain across dispatches with zero
    # extra combine work, in exactly the classic loop's addition order
    # (CPU-bitwise; tests/test_fused_eval.py). At an in-training test
    # boundary the solver takes a cheap on-device copy of the shared
    # param view (the train step DONATES those buffers), dispatches
    # the eval scan, and resumes dispatching train steps immediately;
    # the single device->host sync happens at harvest time and the
    # scores log tagged with the iteration they evaluate — the
    # whole-loop-on-accelerator strategy (arXiv:1810.09868) applied to
    # evaluation, with eval hidden behind training compute the way the
    # reference hides communication behind backprop (arXiv:1810.11112).

    _TEST_SUPER_BATCH_BYTES = 256 << 20  # HBM cap for one eval super-batch

    def _test_net_meta(self, ti: int) -> tuple[tuple, tuple]:
        """(output blobs, param-layer names) for test net `ti` — static
        net properties, computed once instead of rescanned every pass."""
        meta = self._test_meta.get(ti)
        if meta is None:
            tnet = self.test_nets[ti]
            meta = (tuple(self._output_blobs(tnet)),
                    tuple(l.name for l in tnet.layers if l.params))
            self._test_meta[ti] = meta
        return meta

    def _test_chunk_len(self, tnet: Net, iters: int) -> int:
        """T: test batches fused into one eval dispatch. sp.test_chunk
        pins it; 0 (default) auto-sizes: the largest T whose [T, B, ...]
        super-batch stays under _TEST_SUPER_BATCH_BYTES (the feed queue
        double-buffers, so up to two are in flight), capped at 64 to
        keep scan compiles cheap. A pass costs ceil(test_iter/T) scan
        dispatches + 1 param-copy dispatch."""
        k = int(getattr(self.sp, "test_chunk", 0) or 0)
        if k > 0:
            return max(1, min(k, iters))
        bytes_per = 0
        for _key, (shape, kind) in tnet.feed_specs.items():
            n = 1
            for d in shape:
                n *= int(d)
            bytes_per += n * (1 if kind == "uint8" else 4)
        if not bytes_per:  # no feed specs (probe-less nets): blob shapes
            for b in tnet.feed_blobs:
                n = 1
                for d in tnet.blob_shapes.get(b, ()):
                    n *= int(d)
                bytes_per += n * 4
        cap = max(int(self._TEST_SUPER_BATCH_BYTES // max(bytes_per, 1)), 1)
        return max(1, min(iters, cap, 64))

    def _place_test_feeds(self, tree, batch_axis: int):
        """Shard a test feed pytree over the 'data' mesh axis so SPMD
        runs evaluate on ALL chips (pre-ISSUE-2 test batches entered
        unsharded even when training ran on a mesh), replicating when
        the test batch doesn't divide the axis
        (MeshPlan.shard_feeds_or_replicate)."""
        placed, sharded = self.mesh.shard_feeds_or_replicate(
            tree, batch_axis=batch_axis)
        if not sharded and not self._warned_unsharded_test:
            self._warned_unsharded_test = True
            log.info("test batch does not divide the 'data' mesh axis "
                     "(%d); evaluating replicated", self.mesh.n_data)
        return placed

    def _test_feed_queue(self, ti: int, feed_fn):
        """Device feed queue for test net `ti`: assembles + device_puts
        [T, 1, B, ...] eval super-batches in a worker thread (mesh runs
        shard the batch axis; gpipe runs pin to stage-0's device)."""
        queue = self._test_feed_queues.get(ti)
        if queue is not None and queue.feed_fn is not feed_fn:
            queue.close()
            queue = None
        if queue is None:
            from ..data.feeder import DeviceFeedQueue
            place = None
            if self.mesh is not None:
                place = lambda t: self._place_test_feeds(t, batch_axis=2)
            elif self.gpipe is not None:
                dev0 = self.gpipe.devices[0]
                place = lambda t: jax.device_put(t, dev0)
            queue = DeviceFeedQueue(feed_fn, place=place)
            self._test_feed_queues[ti] = queue
        return queue

    def _test_fwd(self, ti: int):
        """Single-batch jitted forward for test net `ti`, reducing every
        output blob to a scalar sum ON DEVICE and returning one stacked
        vector (the reference aggregates on-device too,
        solver.cpp:501-519) — the classic fallback for host-callback
        nets on the CPU backend, and the oracle the fused scan must
        match bitwise."""
        fwd = self._test_fwd_jits.get(ti)
        if fwd is None:
            tnet = self.test_nets[ti]
            out_blobs, _ = self._test_net_meta(ti)

            def fwd_sums(p, s, f, tnet=tnet, out_blobs=out_blobs):
                blobs = tnet.apply(p, s, f, train=False)[0]
                return jnp.stack([jnp.sum(blobs[b]).astype(jnp.float32)
                                  for b in out_blobs])
            fwd = jax.jit(fwd_sums)
            self._test_fwd_jits[ti] = fwd
        return fwd

    def _build_eval_scan(self, ti: int):
        """The fused eval program for test net `ti`:
            (tparams, tstate, feeds_super, acc0) -> acc
        One `lax.scan` over the [T, 1, B, ...] super-batch; the carry is
        the stacked per-blob sum vector, seeded with acc0 = the PREVIOUS
        chunk's result, so a multi-chunk pass accumulates in exactly the
        classic per-batch order with no extra combine dispatches. The
        chained accumulator is donated; the super-batch is not (XLA
        can't alias a scan-consumed operand, and the no-op donation just
        warns)."""
        tnet = self.test_nets[ti]
        out_blobs, _ = self._test_net_meta(ti)

        def eval_scan(tparams, tstate, feeds_super, acc0):
            def body(acc, feeds_stack):
                feeds = jax.tree.map(lambda x: x[0], feeds_stack)
                blobs = tnet.apply(tparams, tstate, feeds, train=False)[0]
                sums = jnp.stack([jnp.sum(blobs[b]).astype(jnp.float32)
                                  for b in out_blobs])
                return acc + sums, None

            acc, _ = jax.lax.scan(body, acc0, feeds_super)
            return acc

        return jax.jit(eval_scan, donate_argnums=(3,))

    def _start_eval(self, test_feed_fns) -> None:
        """Dispatch the FIRST chunk of an evaluation pass per test net,
        WITHOUT the device->host sync. On return `self._pending_eval`
        holds per-net continuation records; training dispatch resumes
        immediately, `_continue_eval()` feeds the remaining eval chunks
        opportunistically between train steps (dispatching only when
        the worker thread has their super-batch ready, so the train
        loop never blocks on eval feed assembly), and `_harvest_eval`
        drains + materializes the scores later. The host time spent
        here (param copy + first-chunk fetch + dispatch) is the
        boundary's eval stall, accumulated in eval_stall_ms."""
        t0 = time.perf_counter()
        entries = []
        settled = False
        for ti, tnet in enumerate(self.test_nets):
            iters = self.sp.test_iter[ti] if ti < len(self.sp.test_iter) \
                else 50
            feed_fn = test_feed_fns[ti]
            out_blobs, _ = self._test_net_meta(ti)
            if not out_blobs or iters == 0:  # degenerate test net
                entries.append(None)
                continue
            # test nets share the train net's weights by layer name
            # (reference ShareTrainedLayersWith)
            tparams = self._shared_params(tnet)
            tstate = self.net_state
            if self.gpipe is not None:
                # stage-placed params are committed to different devices;
                # evaluation runs whole-net on stage-0's device
                dev0 = self.gpipe.devices[0]
                tparams = jax.device_put(tparams, dev0)
                tstate = jax.device_put(tstate, dev0)
            if self._sync_test:
                # host-callback nets on the CPU backend must sync every
                # program (see __init__): classic per-batch loop, scores
                # still harvested through the same pending record
                fwd = self._test_fwd(ti)
                acc = None
                for k in range(iters):
                    feeds = feed_fn(k)
                    if self.mesh is not None:
                        feeds = self._place_test_feeds(feeds, batch_axis=0)
                    sums = fwd(tparams, tstate, feeds)
                    jax.block_until_ready(sums)
                    self.test_dispatch_count += 1
                    acc = sums if acc is None else acc + sums
                entries.append({"ti": ti, "out_blobs": out_blobs,
                                "acc": acc, "iters": iters, "next": iters})
                self.test_pass_count += 1
                continue
            if not settled:
                # the boundary train step may still be in flight with
                # these buffers mid-donation-handoff; dispatching copies
                # against that state intermittently SIGABRTs the CPU
                # client (same hazard, same fix as the async snapshot
                # capture in snapshot()): settle first. Costs
                # the tail of one step, which the eval had to wait out
                # on device anyway.
                jax.block_until_ready((tparams, tstate))
                settled = True
            # point-in-time copy (HBM->HBM, async): the next train step
            # donates the live params/state the moment it dispatches
            copy = lambda a: jnp.copy(a) if isinstance(a, jax.Array) else a
            tparams = jax.tree.map(copy, tparams)
            tstate = jax.tree.map(copy, tstate)
            self.test_dispatch_count += 1  # the shared-param copy
            queue = self._test_feed_queue(ti, feed_fn)
            T = self._test_chunk_len(tnet, iters)
            jit = self._test_eval_jits.get(ti)
            if jit is None:
                jit = self._build_eval_scan(ti)
                self._test_eval_jits[ti] = jit
            acc = jnp.zeros(len(out_blobs), jnp.float32)
            if self.mesh is not None:
                acc = self.mesh.replicate(acc)
            entry = {"ti": ti, "out_blobs": out_blobs, "acc": acc,
                     "iters": iters, "next": 0, "T": T, "queue": queue,
                     "jit": jit, "tparams": tparams, "tstate": tstate}
            # chunk 0 dispatches AT the boundary (its super-batch was
            # prefetched while the boundary train step computed); the
            # rest follow from _continue_eval between train steps
            self._dispatch_eval_chunk(entry)
            entries.append(entry)
            self.test_pass_count += 1
        self._pending_eval = {"iter": self.iter, "entries": entries}
        self.eval_stall_ms += (time.perf_counter() - t0) * 1e3

    def _dispatch_eval_chunk(self, entry) -> None:
        """Fetch + dispatch one eval chunk of `entry`, scheduling the
        following chunk's assembly on the queue worker as the hint."""
        iters, T, queue = entry["iters"], entry["T"], entry["queue"]
        k0 = entry["next"]
        c = min(T, iters - k0)
        left = iters - (k0 + c)
        hint = (k0 + c, min(T, left)) if left > 0 else None
        feeds_super = queue.get(k0, c, hint=hint)
        entry["acc"] = entry["jit"](entry["tparams"], entry["tstate"],
                                    feeds_super, entry["acc"])
        self.test_dispatch_count += 1
        entry["next"] = k0 + c

    def _continue_eval(self, block: bool = False) -> None:
        """Advance an in-flight evaluation pass. Non-blocking mode (the
        per-iteration call in step()) dispatches every chunk whose
        super-batch the worker thread has ALREADY assembled — eval feed
        assembly hides behind train compute and the dispatches
        interleave with train steps. block=True (harvest) drains the
        rest unconditionally."""
        pending = self._pending_eval
        if pending is None:
            return
        t0 = time.perf_counter() if block else 0.0
        for entry in pending["entries"]:
            if entry is None:
                continue
            while entry["next"] < entry["iters"]:
                if not block and not entry["queue"].ready(
                        entry["next"],
                        min(entry["T"], entry["iters"] - entry["next"])):
                    break
                with spans.span("solver/eval dispatch"):
                    self._dispatch_eval_chunk(entry)
        if block:
            self.eval_stall_ms += (time.perf_counter() - t0) * 1e3

    def _harvest_eval(self) -> list[dict[str, float]] | None:
        """Drain and materialize a dispatched evaluation pass: ONE
        device->host transfer per test net (the accumulators), scores
        logged tagged with the iteration they evaluate. Returns the
        results list, or None when nothing is pending. Any wait here
        counts as eval stall — it is ~0 when the pass's chunks already
        dispatched between train steps, because the eval programs
        precede the later train work in device order."""
        if self._pending_eval is None:
            return None
        self._continue_eval(block=True)  # dispatch any remaining chunks
        pending = self._pending_eval
        self._pending_eval = None
        t0 = time.perf_counter()
        results = []
        for entry in pending["entries"]:
            if entry is None:
                results.append({})
                continue
            ti, out_blobs = entry["ti"], entry["out_blobs"]
            with self._guard("eval harvest"):
                vals = np.asarray(entry["acc"]) / entry["iters"]  # host-sync: ok
            # host-sync: ok — vals is already a host ndarray
            scores = {b: float(v) for b, v in zip(out_blobs, vals)}
            if self.rank == 0:
                log.info("Test net #%d, iteration %d:", ti, pending["iter"])
                for b, v in scores.items():
                    # 3-arg format is load-bearing: examples/common.py
                    # self-asserts parse (ti, blob, value) off this line
                    log.info("    Test net #%d: %s = %.5g", ti, b, v)
            results.append(scores)
        self.eval_stall_ms += (time.perf_counter() - t0) * 1e3
        return results

    def _prefetch_test_feeds(self, test_feed_fns) -> None:
        """Warm each test net's first eval super-batch in the feed
        queue's worker thread — called when the iteration just
        dispatched ends at a test boundary, so assembly + device_put
        overlap its compute and the boundary itself only pays
        dispatches."""
        if self._sync_test:
            return
        for ti, tnet in enumerate(self.test_nets):
            iters = self.sp.test_iter[ti] if ti < len(self.sp.test_iter) \
                else 50
            out_blobs, _ = self._test_net_meta(ti)
            if not out_blobs or iters == 0:
                continue
            queue = self._test_feed_queue(ti, test_feed_fns[ti])
            queue.prefetch(0, min(self._test_chunk_len(tnet, iters), iters))

    def test_all(self, test_feed_fns) -> list[dict[str, float]]:
        """Evaluate every test net, averaging output blobs over
        test_iter batches (reference Solver::TestAll/Test). Synchronous
        wrapper over the fused pipeline: an in-flight async pass is
        drained first (its scores log under their own iteration tag),
        then this pass dispatches and harvests."""
        self._harvest_eval()
        with spans.span("solver/eval dispatch"):
            self._start_eval(test_feed_fns)
        return self._harvest_eval()

    def _shared_params(self, tnet: Net):
        """Map train-net params onto a test net by layer name — the
        layer-name list is cached per test net (_test_net_meta), not
        rescanned every pass."""
        try:
            names = self._test_net_meta(self.test_nets.index(tnet))[1]
        except ValueError:  # foreign net (tests): scan directly
            names = tuple(l.name for l in tnet.layers if l.params)
        out = {}
        for name in names:
            if name not in self.params:
                raise KeyError(
                    f"test net layer {name!r} has no matching "
                    "train-net params")
            out[name] = self.params[name]
        return out

    @staticmethod
    def _output_blobs(net: Net) -> list[str]:
        consumed = {b for l in net.layers for b in l.lp.bottom}
        produced = [t for l in net.layers for t in l.lp.top]
        return [t for t in produced if t not in consumed]

    # ------------------------------------------------------------------
    # Snapshot / restore (reference solver.cpp:542-604): two files —
    # weights (.caffemodel / .caffemodel.h5, readable by the reference) +
    # solver state (.solverstate.npz: iter, optimizer history, weights
    # pointer; the reference uses a SolverState binaryproto).
    def snapshot(self, block: bool = True) -> str:
        """Two-file snapshot in the reference's own formats (solver.cpp
        Snapshot; caffe.proto:303-308) — a reference build can resume our
        snapshots and vice versa.

        block=False (mid-training snapshots) hands the write to a
        background thread while training races ahead — a TPU-native
        advantage over the reference, whose snapshot stalls the train
        loop for the full device->host copy + serialize
        (solver.cpp:542-604). The capture is a device-side COPY (HBM to
        HBM, dispatched async): jax arrays are immutable, but the jitted
        step DONATES its input buffers, so the live pytrees' storage is
        invalidated by the very next step — the copy breaks that
        aliasing for a true point-in-time view. The device->host gather
        then runs in the worker thread.

        Multi-host note: the sharded-state gather is collective (all
        ranks enter; only rank 0 writes) and MUST NOT interleave with
        training collectives from another thread — so when exporting
        would require a collective in a multi-process run, async mode
        falls back to blocking (collective order then stays identical on
        every rank)."""
        if str(self.sp.snapshot_format).upper() == "ORBAX":
            # sharded native checkpoints (ISSUE 11): the orbax save is
            # collective in a multi-host run (every rank streams its
            # own shards) and orbax owns its write pipeline — it always
            # runs blocking here so collective order stays
            # rank-identical, like the collective-gather fallback below
            self.wait_snapshots()
            return self.snapshot_native()
        if not block and FAULTS.fire("snapshot_sync") is not None:
            # test-only: force blocking writes so kill/corrupt injection
            # sites land at deterministic iterations
            block = True
        if not block and jax.process_count() > 1 and needs_collective_gather(
                (self.params, self.net_state, self.opt_state)):
            block = True
        if block:
            view = (self.params, self.net_state, self.opt_state, self.iter,
                    self._current_step())
            self.wait_snapshots()
            return self._write_snapshot(*view)
        # Settle the live buffers BEFORE dispatching the copies. The
        # interval snapshot fires right after a step whose execution is
        # still in flight and whose donated inputs are mid-handoff;
        # dispatching jnp.copy against that state intermittently ABORTS
        # inside the runtime (SIGABRT, no Python exception — a 'Fatal
        # Python error' reproduced ~1-in-10 on the 8-virtual-device CPU
        # client of an earlier jax and root-caused to exactly this call
        # stack). Blocking here costs only
        # the tail of one step: the copies could not start earlier
        # anyway, and the device->host gather still runs in the worker.
        with self._guard("snapshot settle"):
            jax.block_until_ready((self.params, self.net_state,
                                   self.opt_state))
        copy = lambda t: jax.tree.map(
            lambda a: jnp.copy(a) if isinstance(a, jax.Array) else a, t)
        with spans.span("solver/snapshot handoff"):
            view = (copy(self.params), copy(self.net_state),
                    copy(self.opt_state), self.iter, self._current_step())
            self.wait_snapshots()  # at most one in flight: writes stay ordered
            self._snapshot_thread = threading.Thread(
                target=self._write_snapshot_guarded, args=view, daemon=True,
                name="snapshot-writer")
            self._snapshot_thread.start()
        return ""

    def wait_snapshots(self, timeout: float = 600.0) -> None:
        """Join any in-flight async snapshot (end of training / before a
        blocking snapshot of the same files). Re-raises a failed async
        write with its snapshot iteration — a checkpoint the user
        believes exists but doesn't must not exit 0, and the error must
        name WHICH interval snapshot is missing. The join is bounded
        (deadline-discipline): a writer wedged inside a device fetch
        that never returns must fail loudly, not hang the exit path."""
        t = getattr(self, "_snapshot_thread", None)
        if t is not None and t.is_alive():
            t.join(timeout)
            if t.is_alive():
                raise RuntimeError(
                    f"async snapshot writer still running after "
                    f"{timeout:g}s — wedged device fetch? The snapshot "
                    f"it was writing must be considered missing")
        err = getattr(self, "_snapshot_error", None)
        if err is not None:
            # lint: ok(thread-shared-mutation) — the writer thread was
            # joined above; the happens-before edge is the join
            self._snapshot_error = None
            it, exc = err
            raise RuntimeError(
                f"async snapshot failed at iteration {it}") from exc

    def _write_snapshot_guarded(self, *view) -> None:
        try:
            self._write_snapshot(*view)
        except BaseException as e:  # surfaced by wait_snapshots
            # lint: ok(thread-shared-mutation) — single writer thread,
            # and wait_snapshots() JOINS it before reading/clearing, so
            # the happens-before edge is the join, not a lock
            self._snapshot_error = (view[3], e)

    def _write_snapshot(self, params, net_state, opt_state, it,
                        current_step) -> str:
        """Verified atomic snapshot (ISSUE 3): each file is written to a
        temp path and `os.replace`d into place, then a crc32c sidecar
        manifest is published LAST — so a kill at ANY point leaves
        either a complete, verifiable snapshot or no manifest at all
        (and the previous snapshot loadable). After the manifest lands,
        the run manifest's resume pointer advances and `snapshot_keep`
        GC sweeps old snapshots (never the newest verified one)."""
        from .. import io as caffe_io
        if self.rank != 0 and not needs_collective_gather(
                (params, net_state, opt_state)):
            # non-root with nothing collective to contribute: skip the
            # full model device->host copy
            return ""
        with self._guard("snapshot gather"):
            weights = self.net.export_weights(params, net_state)
            history = self._history_blobs(opt_state)
        if self.rank != 0:  # only root writes (solver.cpp:543)
            return ""
        prefix = self.sp.snapshot_prefix or "snapshot"
        os.makedirs(os.path.dirname(prefix) or ".", exist_ok=True)
        layer_types = {l.name: l.lp.type for l in self.net.layers}
        if str(self.sp.snapshot_format).upper() == "HDF5":
            model_path = f"{prefix}_iter_{it}.caffemodel.h5"
            with resilience.atomic_output(model_path) as tmp:
                caffe_io.save_caffemodel_h5(tmp, weights)
            FAULTS.maybe_exit("snapshot_kill")  # test-only: die mid-write
            state_path = f"{prefix}_iter_{it}.solverstate.h5"
            with resilience.atomic_output(state_path) as tmp:
                caffe_io.save_solverstate_h5(tmp, it, model_path,
                                             history, current_step)
        else:
            model_path = f"{prefix}_iter_{it}.caffemodel"
            with resilience.atomic_output(model_path) as tmp:
                caffe_io.save_caffemodel(tmp, weights,
                                         self.net.name, layer_types)
            FAULTS.maybe_exit("snapshot_kill")  # test-only: die mid-write
            state_path = f"{prefix}_iter_{it}.solverstate"
            with resilience.atomic_output(state_path) as tmp:
                caffe_io.save_solverstate(tmp, it, model_path,
                                          history, current_step)
        manifest = resilience.write_snapshot_manifest(
            state_path, it, {"model": model_path, "state": state_path})
        # test-only: post-manifest bitrot — the crc check on load must
        # catch it and resume must fall back to an older snapshot
        FAULTS.corrupt_file("snapshot_corrupt", model_path)
        # lint: ok(thread-shared-mutation) — at most one snapshot writer
        # is ever in flight (wait_snapshots() joins the previous one
        # before the next dispatch or any blocking write starts)
        self._last_snapshot = (it, state_path)
        self._journal_run_state("snapshot")
        if jax.process_count() > 1:
            # ISSUE 11: fold the per-host quarantine journals into the
            # classic audit file at the same snapshot cadence
            resilience.merge_quarantine_journals(prefix)
        keep = int(getattr(self.sp, "snapshot_keep", 0) or 0)
        if keep > 0:
            # assume_verified: this writer checksummed `manifest`'s files
            # moments ago — don't re-read the whole model for the GC scan
            resilience.gc_snapshots(prefix, keep, assume_verified=manifest)
        log.info("Snapshotting to %s + %s (manifest %s)", model_path,
                 state_path, os.path.basename(manifest))
        return state_path

    @staticmethod
    def _to_host(a) -> np.ndarray:
        """See parallel.mesh.to_host_array — gathers remote shards
        (multi-host ZeRO-1 slots / TP weights) before the host copy."""
        from ..parallel.mesh import to_host_array
        return to_host_array(a)

    def _history_blobs(self, opt_state=None) -> list:
        """Optimizer slots as the reference's flat history list: params in
        net order, slot-major (history[i + s*N] = slot s of param i;
        sgd_solver.cpp PreSolve + adam_solver.cpp:37-39)."""
        if opt_state is None:
            opt_state = self.opt_state
        decls = list(self.net.learnable_param_decls())
        slots_per = max((len(opt_state[l][p]) for l, p, _ in decls),
                        default=0)
        out = []
        for s in range(slots_per):
            for lname, pname, _ in decls:
                out.append(self._to_host(opt_state[lname][pname][s]))
        return out

    def _current_step(self) -> int:
        """Reference current_step_: multistep stage index (solver.cpp)."""
        if str(self.sp.lr_policy) == "multistep":
            return sum(1 for v in self.sp.stepvalue if self.iter >= v)
        return 0

    # -- TPU-native sharded checkpointing (orbax) ----------------------
    # The .caffemodel/.solverstate path above GATHERS every array to host
    # rank 0 for reference interop — correct, but at 16-chip TP scale the
    # gather (and the single-host RAM to hold it) is a bottleneck the
    # single-device-model reference never had to face. The native path
    # writes each array per-shard from the devices that own it (orbax /
    # tensorstore) and restores with shardings preserved.

    def snapshot_native(self, path: str | None = None) -> str:
        """Sharded checkpoint of the FULL training state (params +
        optimizer slots + BN state + iter). No host gather: each shard
        streams from its device. Returns the checkpoint directory.

        Verified-atomic since ISSUE 11: after the (collective) orbax
        save, every host syncs at a write barrier, then rank 0 ALONE
        publishes the per-shard crc32c manifest — the commit record, so
        "manifest exists" == "every host's shards landed" — advances
        the run journal's resume pointer, merges per-host quarantine
        journals, and runs `snapshot_keep` GC (which sweeps whole
        .orbax dirs, never the newest verified set)."""
        import orbax.checkpoint as ocp
        prefix = self.sp.snapshot_prefix or "snapshot"
        it = self.iter
        path = path or f"{prefix}_iter_{it}.orbax"
        path = os.path.abspath(path)
        with self._guard("snapshot settle"):
            # same aliasing hazard as the flat path: the save must not
            # read buffers a still-in-flight step is about to donate
            jax.block_until_ready((self.params, self.net_state,
                                   self.opt_state))
        with ocp.StandardCheckpointer() as ckptr:
            ckptr.save(path, {
                "params": self.params,
                "opt_state": self.opt_state,
                "net_state": self.net_state,
                "iter": jnp.asarray(it, jnp.int32),
            }, force=True)
        if jax.process_count() > 1:
            # all-hosts write barrier BEFORE the commit record: a
            # manifest covering shards a slow host has not flushed yet
            # would verify against a torn set
            from ..parallel.mesh import cluster_barrier
            if not cluster_barrier(f"caffe_snapshot_{it}"):
                raise resilience.ClusterError(
                    f"sharded-snapshot write barrier failed at "
                    f"iteration {it} (peer host lost mid-checkpoint?)")
        if self.rank != 0:
            return path
        manifest = resilience.write_sharded_manifest(path, it)
        if FAULTS.active("snapshot_shard_corrupt"):
            # test-only: post-manifest bitrot in ONE shard — restore
            # must reject the whole set and fall back
            shards = resilience.sharded_snapshot_files(path)
            if shards:
                FAULTS.corrupt_file("snapshot_shard_corrupt", shards[0])
        # lint: ok(thread-shared-mutation) — blocking path: callers run
        # wait_snapshots() first, so no async writer is in flight
        self._last_snapshot = (it, path)
        self._journal_run_state("snapshot")
        if jax.process_count() > 1:
            resilience.merge_quarantine_journals(prefix)
        keep = int(getattr(self.sp, "snapshot_keep", 0) or 0)
        if keep > 0:
            resilience.gc_snapshots(prefix, keep,
                                    assume_verified=manifest)
        log.info("Native sharded snapshot to %s (manifest %s)", path,
                 os.path.basename(manifest))
        return path

    def restore_native(self, path: str) -> None:
        """Restore a snapshot_native checkpoint; every array comes back
        with the sharding the current solver places it at (replicated or
        the TP rules), read per-shard."""
        import orbax.checkpoint as ocp

        # every leaf gets an explicit CURRENT-topology sharding: letting
        # orbax fall back to the sharding recorded in the file would pin
        # the restore to the checkpoint's topology
        if self.mesh is not None:
            default_sharding = self.mesh.replicated()
        else:
            from jax.sharding import SingleDeviceSharding
            default_sharding = SingleDeviceSharding(jax.devices()[0])

        def abstract(tree):
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    np.shape(a), a.dtype,
                    sharding=getattr(a, "sharding", default_sharding))
                if hasattr(a, "dtype") else a, tree)

        target = {
            "params": abstract(self.params),
            "opt_state": abstract(self.opt_state),
            "net_state": abstract(self.net_state),
            "iter": jax.ShapeDtypeStruct((), jnp.int32,
                                         sharding=default_sharding),
        }
        with ocp.StandardCheckpointer() as ckptr:
            state = ckptr.restore(os.path.abspath(path), target)
        self.params = state["params"]
        self.opt_state = state["opt_state"]
        self.net_state = state["net_state"]
        self.iter = int(state["iter"])
        # same post-restore contract as restore(): clean guard counters
        # — a rewind exists to escape the divergence, not re-trip on it
        self._gstate = None
        self._guard_prev = None
        log.info("Restored native snapshot from %s (iter %d)", path,
                 self.iter)

    def restore_auto(self, prefix: str | None = None) -> str | None:
        """Resume from the newest VERIFIED snapshot for `prefix` (the
        `--resume auto` entry point). Scans the crc32c manifests newest
        first; corrupt or unloadable candidates are logged and skipped —
        the fall-back-to-newest-prior-verified half of the snapshot
        contract. Pre-manifest snapshots (written before the verified-
        atomic scheme) are tried last, unverified. Returns the restored
        state path, or None when no usable snapshot exists (caller
        starts fresh)."""
        prefix = prefix or self.sp.snapshot_prefix or "snapshot"
        run = resilience.read_run_manifest(prefix)
        if run is not None:
            log.info("run manifest %s: previous run ended at iter %s "
                     "(reason %r)", resilience.run_manifest_path(prefix),
                     run.get("iter"), run.get("reason"))
        manifested: set[str] = set()
        for it, mpath in resilience.iter_snapshot_manifests(prefix):
            doc = resilience.verify_snapshot(mpath)
            if doc is None:
                log.warning("snapshot at iter %d failed crc verification "
                            "(corrupt or incomplete); falling back to an "
                            "older snapshot", it)
                continue
            manifested.add(os.path.abspath(doc["state"]))
            try:
                self.restore(doc["state"], verify=False)
            # lint: ok(typed-failure) — falling back to an older
            # verified snapshot IS the recovery path (docs/robustness)
            except Exception:
                log.exception("verified snapshot at iter %d failed to "
                              "load; falling back", it)
                continue
            # lint: ok(thread-shared-mutation) — resume happens before
            # training starts; no snapshot writer exists yet
            self._last_snapshot = (it, doc["state"])
            return doc["state"]
        # legacy snapshots with no manifest sidecar: newest iteration
        # first, skipping states a (failed) manifest already covers —
        # re-trying those unverified would resurrect known-bad bytes.
        # Pre-ISSUE-11 .orbax dirs (written before the sharded-manifest
        # scheme) are candidates the same way.
        import re
        d = os.path.dirname(prefix) or "."
        stem = os.path.basename(prefix) + "_iter_"
        pat = re.compile(re.escape(stem)
                         + r"(\d+)(\.solverstate(\.h5)?|\.orbax)$")
        cands = []
        try:
            for name in os.listdir(d):
                m = pat.match(name)
                if m:
                    cands.append((int(m.group(1)), os.path.join(d, name)))
        except OSError:
            cands = []
        for it, path in sorted(cands, reverse=True):
            mp = resilience.manifest_for_state(path)
            if os.path.abspath(path) in manifested or (
                    mp and os.path.exists(mp)):
                continue
            try:
                self.restore(path, verify=False)
            # lint: ok(typed-failure) — falling back to an older
            # snapshot IS the recovery path; exhaustion raises below
            except Exception:
                log.exception("legacy snapshot %s failed to load; "
                              "falling back", path)
                continue
            log.warning("resumed from legacy (unverified) snapshot %s",
                        path)
            # lint: ok(thread-shared-mutation) — resume happens before
            # training starts; no snapshot writer exists yet
            self._last_snapshot = (it, path)
            return path
        log.info("no usable snapshot under prefix %r; starting fresh",
                 prefix)
        return None

    def restore(self, path: str, *, verify: bool = True) -> None:
        """Resume from a .solverstate{,.h5,.npz} (reference
        Solver::Restore / SGDSolver::RestoreSolverStateFromBinaryProto).
        Reads reference-written binaryproto states directly; .orbax
        directories route to the native sharded path. When a crc32c
        manifest sidecar exists for the state (verified-atomic
        snapshots, ISSUE 3), the snapshot is verified before any bytes
        are loaded; corruption raises SnapshotCorruptError (use
        restore_auto for the fall-back-to-older behavior). Manifest-less
        snapshots load unverified, as before."""
        with spans.phase("solver/restore", source=os.path.basename(
                path.rstrip("/"))):
            self._restore(path, verify)

    def _restore(self, path: str, verify: bool) -> None:
        if verify:
            # .orbax dirs share the manifest scheme since ISSUE 11
            # (per-shard crc entries) — verify them the same way
            mpath = resilience.manifest_for_state(path)
            if mpath is not None and os.path.exists(mpath):
                if resilience.verify_snapshot(mpath) is None:
                    raise resilience.SnapshotCorruptError(
                        f"snapshot {path} failed crc32c verification "
                        f"against {mpath}; resume with --resume auto to "
                        "fall back to the newest prior verified snapshot")
        if path.rstrip("/").endswith(".orbax"):
            return self.restore_native(path)
        from .. import io as caffe_io
        if path.endswith(".npz"):  # this framework's pre-interop format
            data = np.load(path)
            self.iter = int(data["meta/iter"])
            model_path = str(data["meta/model"])
            self._load_snapshot_weights(model_path, path)
            for key in data.files:
                parts = key.split("/")
                if parts[0] == "opt":
                    _, lname, pname, si = parts
                    slots = list(self.opt_state[lname][pname])
                    slots[int(si)] = jnp.asarray(data[key])
                    self.opt_state[lname][pname] = tuple(slots)
        else:
            loader = (caffe_io.load_solverstate_h5
                      if path.endswith((".h5", ".hdf5"))
                      else caffe_io.load_solverstate)
            it, learned_net, history, _step = loader(path)
            self.iter = it
            if learned_net:
                self._load_snapshot_weights(learned_net, path)
            decls = list(self.net.learnable_param_decls())
            n = len(decls)
            slots_per = len(self.opt_state[decls[0][0]][decls[0][1]]) \
                if decls else 0
            # strict like the reference's CHECK_EQ on history size
            # (sgd_solver.cpp:324): a bank-count mismatch means the
            # snapshot came from a different solver type
            if len(history) != n * slots_per:
                raise ValueError(
                    f"solverstate history has {len(history)} blobs; this "
                    f"solver expects {n} params x {slots_per} slots = "
                    f"{n * slots_per} (snapshot from a different solver "
                    "type?)")
            for i, (lname, pname, _) in enumerate(decls):
                cur = self.opt_state[lname][pname]
                new = []
                for s in range(len(cur)):
                    arr = history[i + s * n].reshape(np.shape(cur[s]) or ())
                    new.append(jnp.asarray(arr, cur[s].dtype
                                           if hasattr(cur[s], "dtype")
                                           else None))
                self.opt_state[lname][pname] = tuple(new)
        self._place_params_opt()
        # ISSUE 4: a restored run starts with clean guard counters — a
        # rewind exists to escape the divergence, not to instantly
        # re-trip on the previous attempt's consecutive-skip count
        self._gstate = None
        self._guard_prev = None
        log.info("Restored solver state from %s (iter %d)", path, self.iter)

    def _load_snapshot_weights(self, model_path: str, state_path: str) -> None:
        """learned_net paths are stored as written (often relative to the
        training cwd); fall back to resolving next to the state file."""
        if not os.path.exists(model_path):
            cand = os.path.join(os.path.dirname(os.path.abspath(state_path)),
                                os.path.basename(model_path))
            if os.path.exists(cand):
                model_path = cand
        self.load_weights(model_path)

    def load_weights(self, path: str) -> None:
        """Finetune-style weight load (reference `caffe train -weights`)."""
        from .. import io as caffe_io
        with spans.phase("solver/restore", source=os.path.basename(path)):
            weights = caffe_io.load_weights(path)
            self.params, self.net_state = self.net.import_weights(
                self.params, self.net_state, weights)
            if self.mesh is not None:
                self.net_state = self.mesh.replicate(self.net_state)
            self._place_params_opt()
        log.info("Loaded weights from %s (%d layers)", path, len(weights))
