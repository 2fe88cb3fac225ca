"""Overlapped bucketed gradient reduction — the reference's
ReduceAndUpdate plane (`src/caffe/net.cpp:757-913`) rebuilt as explicit
per-bucket collectives inside the jitted train step.

Reference mechanics being replaced: backward emits param ids in reverse
topological order into a dedicated reduce thread; the thread packs
contiguous gradients from the shared learnable-diff space
(`net.cpp:1350-1374`) into `reduce_buckets` (default 6, caffe.proto:140)
buckets of ~total_count/reduce_buckets elements and ncclAllReduces each
bucket on a high-priority stream WHILE backward still runs
(`Reduce:880`, `ReduceBucket:899`), scaling by 1/solver_count after the
reduce (`net.cpp:891,910`). That overlap of reduction with remaining
backprop is where distributed-SGD scaling lives (arXiv:1810.11112).

TPU-native equivalent: the default mesh path leaves the gradient
all-reduce IMPLICIT — GSPMD inserts per-parameter collectives wherever
dataflow demands, typically combined into one end-of-step reduction.
This module makes the reference's structure explicit so the compiler's
latency-hiding scheduler has independent collectives to hoist
(arXiv:1810.09868: express the communication, let XLA overlap it):

- `plan_buckets`: pack learnable params into contiguous buckets in
  reverse topological layer order — the order backward produces their
  gradients — sized by `reduce_buckets` count or a `grad_bucket_mb`
  byte budget (the diff-space packing, minus the shared allocation).
- `bucketed_value_and_grad`: an opt-in `shard_map` variant of the
  solver's loss/grad computation: each device differentiates its local
  batch shard, then each bucket is flattened into one contiguous
  buffer and `lax.psum`'d over the 'data' axis — one independent
  collective per bucket, issued as soon as its layers' backward
  contributions exist. Dividing by the axis size after the psum
  reproduces the reference's post-reduce 1/solver_count scale, and is
  exact when the axis size is a power of two — accepted steps are then
  BITWISE equal on CPU to the implicit GSPMD path
  (tests/test_reduction.py).
- `unsupported_reason`: the static compatibility gate. The per-device
  backward changes semantics for cross-batch computations, so nets
  with BatchNorm (global-batch statistics), MoE (batch-wide routing
  capacity), host-callback layers, or data-dependent loss
  normalization (SoftmaxWithLoss VALID + ignore_label, normalization
  NONE) fall back to the implicit reduction with a warning. Dropout
  under the bucketed step draws per-device masks (the rng folds in
  `axis_index`) — the reference's per-GPU-mask behavior, statistically
  equivalent but not bitwise vs the global-mask implicit path.
- `collective_stats`: CPU-visible measurement — counts all-reduce ops
  in compiled HLO text and where they sit in program order, so the
  ≥ `reduce_buckets` collectives-per-step claim (and the overlap-span
  proxy) is checkable without a device.

Multi-host (ISSUE 11): the bucket psums reduce over the mesh 'data'
axis, and under `caffe train -hosts N` that axis spans processes — so
each bucket's collective crosses hosts over DCN with NO change to this
module, exactly the reference's global (multi-node) NCCL communicator
(parallel.cpp:166-169) at bucket granularity.
Solver.reduction_stats() adds the `hosts` /
`cross_host_collectives_per_step` facts (this module stays jax-free).
"""

from __future__ import annotations

import logging
import re
from dataclasses import dataclass

import numpy as np

log = logging.getLogger("caffe_mpi_tpu.parallel.reduction")


# ---------------------------------------------------------------------------
# Bucket planning
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Bucket:
    """One contiguous reduction unit: entries are (layer, param) keys in
    reverse-topo order, all the same dtype (a psum'd buffer is one
    buffer); nbytes is the packed size."""
    entries: tuple[tuple[str, str], ...]
    sizes: tuple[int, ...]       # element counts, aligned with entries
    dtype: str
    nbytes: int


@dataclass(frozen=True)
class ReductionPlan:
    """The bucket schedule plus the mesh facts the packed psum needs.

    wire_dtype (ISSUE 9): when set (e.g. "bfloat16" under `precision:
    bf16`), each packed bucket is CAST to this dtype before its psum —
    the collective moves half the bytes — and cast back to the gradient
    dtype right after, so the post-psum 1/n scale, clipping, and the
    optimizer update all run in f32. None (default) reduces in the
    gradient's own dtype, bitwise-identical to before the knob."""
    buckets: tuple[Bucket, ...]
    n_data: int
    axis: str = "data"
    wire_dtype: str | None = None

    @property
    def bucket_bytes(self) -> tuple[int, ...]:
        return tuple(b.nbytes for b in self.buckets)

    @property
    def collectives_per_step(self) -> int:
        """Gradient collectives one micro-step issues (the loss psum is
        not counted — it exists on both paths' display plumbing)."""
        return len(self.buckets)

    def stats(self) -> dict:
        out = {
            "mode": "bucketed",
            "reduce_buckets": len(self.buckets),
            "collectives_per_step": self.collectives_per_step,
            "bucket_bytes": list(self.bucket_bytes),
            "n_data": self.n_data,
        }
        if self.wire_dtype:
            out["wire_dtype"] = self.wire_dtype
        return out

    def psum_buckets(self, grads, pred=None):
        """Reduce a congruent grad pytree bucket-by-bucket inside
        shard_map: flatten each bucket into one contiguous buffer
        (the learnable-diff-space packing, net.cpp:1350-1374), one
        `lax.psum` per bucket, then the exact post-reduce 1/n scale
        (net.cpp:891,910).

        `pred` (a traced, always-true scalar) keeps the unpacked grads
        BITWISE equal to the implicit path's: a reduction fused over a
        slice of the flat bucket buffer sums in a different lane order
        than over a standalone array on the CPU backend (measured ~1
        ulp on `sqrt(sum(square(.)))` — exactly the clip_gradients
        global norm), so the unpack runs inside a `lax.cond` branch: a
        separate HLO computation XLA fusion cannot cross, making each
        grad leaf a materialized buffer just like an all-reduce output.
        Same recipe as the solver's train_guard — and as there,
        `lax.optimization_barrier` does NOT survive the CPU pipeline,
        and the two branches are extensionally identical but
        structurally distinct (the else-arm unpacks through flipped
        buffers) so no simplifier can fold the conditional away while
        a mispredicted branch would still return correct values."""
        import jax.numpy as jnp
        from jax import lax

        wire = self.wire_dtype
        reds = []
        for bucket in self.buckets:
            parts = [grads[ln][pn].reshape(-1)
                     for (ln, pn) in bucket.entries]
            flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            if wire and str(flat.dtype) != wire:
                # ISSUE 9: the collective moves bf16 — half the bytes on
                # the wire; everything after the psum is f32 again
                flat = flat.astype(wire)
            red = lax.psum(flat, self.axis)
            if wire:
                red = red.astype(jnp.float32)
            if self.n_data > 1:
                red = red / self.n_data
            reds.append(red)

        def unpack(reds, mirror=False):
            out = {ln: dict(lp) for ln, lp in grads.items()}
            for red, bucket in zip(reds, self.buckets):
                total = sum(bucket.sizes)
                src = jnp.flip(red) if mirror else red
                off = 0
                for (ln, pn), size in zip(bucket.entries, bucket.sizes):
                    if mirror:
                        piece = jnp.flip(src[total - off - size:
                                             total - off])
                    else:
                        piece = src[off:off + size]
                    out[ln][pn] = piece.reshape(grads[ln][pn].shape)
                    off += size
            return out

        if pred is None:
            return unpack(reds)
        return lax.cond(pred, unpack,
                        lambda rs: unpack(rs, mirror=True), reds)


def plan_buckets(entries, *, n_buckets: int = 0,
                 bucket_bytes: int = 0, n_data: int = 1,
                 axis: str = "data",
                 wire_dtype: str | None = None) -> ReductionPlan:
    """Pack `entries` — an iterable of (layer, param, shape, dtype) in
    REVERSE topological layer order, i.e. the order backward produces
    gradients — into contiguous buckets.

    Exactly one sizing mode applies: `bucket_bytes` > 0 packs greedily
    up to the byte budget (a single param larger than the budget gets
    its own bucket, with a warning — it cannot be split without losing
    the one-collective-per-bucket structure); otherwise `n_buckets`
    splits the total bytes into ~equal targets, the reference's
    total_count/reduce_buckets rule (net.cpp:824-863). dtype changes
    always start a new bucket (one psum buffer is one dtype).
    """
    if bucket_bytes <= 0 and n_buckets <= 0:
        raise ValueError("plan_buckets needs n_buckets > 0 or "
                         "bucket_bytes > 0")
    ents = []
    for (lname, pname, shape, dtype) in entries:
        # wire_dtype (ISSUE 9): buckets pack and travel in this dtype —
        # sizing, budgets, and the reported bucket_bytes follow it
        dt = np.dtype(wire_dtype) if wire_dtype else np.dtype(dtype)
        size = int(np.prod(shape, dtype=np.int64)) if shape else 1
        ents.append((lname, pname, size, dt))
    if not ents:
        return ReductionPlan(buckets=(), n_data=n_data, axis=axis,
                             wire_dtype=wire_dtype)

    total = sum(s * dt.itemsize for (_, _, s, dt) in ents)
    buckets: list[Bucket] = []
    cur: list[tuple] = []
    cur_bytes = 0

    def flush():
        nonlocal cur, cur_bytes
        if cur:
            buckets.append(Bucket(
                entries=tuple((l, p) for (l, p, _, _) in cur),
                sizes=tuple(s for (_, _, s, _) in cur),
                dtype=str(cur[0][3]), nbytes=cur_bytes))
            cur, cur_bytes = [], 0

    if bucket_bytes > 0:
        # byte-budget mode: greedy fill; an oversized param cannot be
        # split without losing the one-collective-per-bucket structure
        target = int(bucket_bytes)
        for (lname, pname, size, dt) in ents:
            nbytes = size * dt.itemsize
            if cur and (str(cur[0][3]) != str(dt)
                        or cur_bytes + nbytes > target):
                flush()
            if nbytes > target:
                log.warning(
                    "param %s/%s (%d bytes) exceeds the grad_bucket_mb "
                    "budget (%d bytes); it gets its own bucket",
                    lname, pname, nbytes, target)
            cur.append((lname, pname, size, dt))
            cur_bytes += nbytes
            if cur_bytes >= target:
                flush()
        flush()
    else:
        # count mode: close bucket b when cumulative bytes cross
        # (b+1)/k of the total (the reference's ~total_count/k rule,
        # net.cpp:824-863), also closing early when the remaining
        # entries are only just enough to populate the remaining
        # buckets — so k buckets come out whenever k <= n_params
        k = min(int(n_buckets), len(ents))
        cum = 0
        for i, (lname, pname, size, dt) in enumerate(ents):
            nbytes = size * dt.itemsize
            if cur and str(cur[0][3]) != str(dt):
                flush()
            cur.append((lname, pname, size, dt))
            cur_bytes += nbytes
            cum += nbytes
            remaining = len(ents) - i - 1
            still_needed = k - len(buckets) - 1
            if len(buckets) < k - 1 and (
                    cum >= (len(buckets) + 1) * total / k
                    or remaining <= still_needed):
                flush()
        flush()
    return ReductionPlan(buckets=tuple(buckets), n_data=n_data, axis=axis,
                         wire_dtype=wire_dtype)


def plan_for_net(net, params, *, n_buckets: int = 0,
                 bucket_bytes: int = 0, n_data: int = 1,
                 wire_dtype: str | None = None) -> ReductionPlan:
    """Bucket plan over a Net's param pytree, layers reversed (backward
    order). Every leaf of `params` must land in exactly one bucket —
    clipping consumes the whole grad tree, so an uncovered leaf would
    silently carry an UNREDUCED per-device gradient into the global
    norm."""
    entries = []
    seen = set()
    for layer in reversed(net.layers):
        lparams = params.get(layer.name)
        if not lparams:
            continue
        if layer.name in seen:
            continue
        seen.add(layer.name)
        for pname, arr in lparams.items():
            entries.append((layer.name, pname, np.shape(arr),
                            getattr(arr, "dtype", np.float32)))
    covered = {(l, p) for (l, p, _, _) in entries}
    want = {(ln, pn) for ln, lp in params.items() for pn in lp}
    missing = want - covered
    if missing:
        raise ValueError(
            f"bucket planner lost params {sorted(missing)} — params "
            "exist outside the net's layer list")
    return plan_buckets(entries, n_buckets=n_buckets,
                        bucket_bytes=bucket_bytes, n_data=n_data,
                        wire_dtype=wire_dtype)


# ---------------------------------------------------------------------------
# Compatibility gate
# ---------------------------------------------------------------------------

# losses whose normalizer is a STATIC batch-proportional count, so the
# per-device backward's cotangent is exactly n x the global one (the
# property the post-psum 1/n scale inverts exactly when n is a power of
# two). Everything else falls back to the implicit reduction.
_DP_SAFE_LOSSES = {
    "SoftmaxWithLoss", "EuclideanLoss", "L1Loss",
    "SigmoidCrossEntropyLoss", "HingeLoss", "MultinomialLogisticLoss",
    "InfogainLoss", "ContrastiveLoss",
}
# layer types whose TRAIN computation couples examples ACROSS the batch
# (per-device execution would change semantics, not just schedule)
_CROSS_BATCH_TYPES = {"BatchNorm", "MoE"}


def _walk_layer_params(lp):
    """Yield every LayerParameter reachable from `lp`, descending into
    composite (Pipeline) bodies."""
    yield lp
    pp = getattr(lp, "pipeline_param", None)
    if pp is not None:
        for inner in pp.layer:
            yield from _walk_layer_params(inner)


def unsupported_reason(net) -> str | None:
    """None when the net's TRAIN graph is safe for the bucketed
    per-device backward; else a human-readable reason (the solver logs
    it and falls back to the implicit reduction)."""
    for layer in net.layers:
        if getattr(layer, "host_callback", False):
            return (f"layer {layer.name!r} re-enters the host from "
                    "inside the step (host_callback)")
        for lp in _walk_layer_params(layer.lp):
            if lp.type in _CROSS_BATCH_TYPES:
                return (f"layer {lp.name!r} ({lp.type}) couples examples "
                        "across the batch; per-device backward would "
                        "change its semantics")
        if not (hasattr(layer, "is_loss") and layer.is_loss()):
            continue
        ltype = layer.lp.type
        if ltype not in _DP_SAFE_LOSSES:
            return (f"loss layer {layer.name!r} ({ltype}) is not on the "
                    "static-normalization allowlist")
        p = layer.lp.loss_param
        mode = ""
        if p is not None and p.has("normalization"):
            mode = str(p.normalization).upper()
        if mode == "NONE":
            return (f"loss layer {layer.name!r} uses normalization NONE "
                    "(sum, not batch-mean)")
        ignore = p.ignore_label if p is not None and p.has("ignore_label") \
            else None
        if ignore is not None and ltype == "SoftmaxWithLoss" \
                and mode in ("", "VALID"):
            return (f"loss layer {layer.name!r} normalizes by a "
                    "data-dependent valid count (ignore_label + VALID)")
    return None


# ---------------------------------------------------------------------------
# The overlapped step
# ---------------------------------------------------------------------------

def bucketed_value_and_grad(loss_fn, mesh_plan, plan: ReductionPlan):
    """Drop-in replacement for `jax.value_and_grad(loss_fn,
    has_aux=True)` in the solver's iteration body, for loss_fn of
    signature (params, net_state, feeds, rng) -> (scaled_loss,
    (net_state, loss)).

    The returned function runs the forward/backward per device on the
    local 'data'-axis batch shard under shard_map, reduces the grads
    per bucket (plan.psum_buckets), and psum-averages the loss — the
    reference's reduce-thread consumer loop (net.cpp:757-913) as
    compiler-schedulable dataflow. The rng folds in the device's axis
    index so stochastic layers draw per-device masks (the reference's
    per-GPU behavior)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..utils import spans

    n = plan.n_data
    axis = plan.axis

    def local(params, net_state, feeds, rng):
        idx = lax.axis_index(axis)
        rng = jax.random.fold_in(rng, idx)
        (scaled, (new_state, loss)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, net_state, feeds, rng)
        # idx >= 0 is traced-but-always-true: it gates the bitwise
        # unpack isolation (see psum_buckets), never the values
        with jax.named_scope(spans.REDUCE):
            grads = plan.psum_buckets(grads, pred=idx >= 0)
            if n > 1:
                scaled = lax.psum(scaled, axis) / n
                loss = lax.psum(loss, axis) / n
        return (scaled, (new_state, loss)), grads

    def vg(params, net_state, feeds, rng):
        fspecs = jax.tree.map(
            lambda x: P(*((axis,) + (None,) * (jnp.ndim(x) - 1))), feeds)
        fn = jax.shard_map(local, mesh=mesh_plan.mesh,
                           in_specs=(P(), P(), fspecs, P()),
                           # everything returned is replicated: grads/loss
                           # are psum'd, net_state is batch-independent by
                           # the unsupported_reason gate
                           out_specs=P(), check_vma=False)
        return fn(params, net_state, feeds, rng)

    return vg


# ---------------------------------------------------------------------------
# Measurement + TPU scheduling knobs
# ---------------------------------------------------------------------------

# `%ar = <type> all-reduce(%a, %b), ...`: <type> is an array type or,
# once XLA's all-reduce combiner has merged several reductions into one
# op, a parenthesized tuple of them (with spaces) — match on the op name
# and read the operand list, never the type
_AR_RE = re.compile(r"=.*?\sall-reduce(?:-start)?\(([^)]*)\)")


def collective_stats(hlo_text: str) -> dict:
    """Count all-reduce ops in compiled HLO text and report where they
    sit in program order. `all_reduces` counts ops; `reduced_buffers`
    counts their operands — the combiner may merge every per-bucket psum
    into one tuple-typed op (jax 0.9.0's CPU pipeline does), and each
    bucket is then one operand of it. `overlap_span` — (last - first
    all-reduce position) / program length — is a structural stand-in
    only: a single end-of-step reduction scores ~0, collectives spread
    through the backward score high. Whether collectives actually hide
    behind compute is read from a device trace (ROADMAP Speed 8)."""
    lines = hlo_text.splitlines()
    hits = [(i, m) for i, line in enumerate(lines)
            if (m := _AR_RE.search(line))]
    idx = [i for i, _ in hits]
    total = max(len(lines), 1)
    return {
        "all_reduces": len(idx),
        "reduced_buffers": sum(len([a for a in m.group(1).split(",")
                                    if a.strip()]) for _, m in hits),
        "first_frac": round(idx[0] / total, 4) if idx else None,
        "last_frac": round(idx[-1] / total, 4) if idx else None,
        "overlap_span": round((idx[-1] - idx[0]) / total, 4) if idx
        else 0.0,
    }


def tpu_overlap_flags() -> list[str]:
    """libtpu compiler flags that help the TPU scheduler hide the
    per-bucket collectives behind remaining backward compute. These are
    TPU-compiler flags, NOT XLA_FLAGS entries — this jaxlib's CPU/GPU
    flag parser hard-fails on them (parse_flags_from_env.cc:226), so
    `caffe train -reduce_overlap` appends them to LIBTPU_INIT_ARGS
    before backend init: only libtpu ever reads that env var, making
    the append a no-op on CPU runs and the dryrun.
    CAFFE_TPU_NO_OVERLAP_FLAGS=1 opts out if a libtpu build rejects
    one."""
    return [
        "--xla_tpu_enable_latency_hiding_scheduler=true",
        "--xla_tpu_enable_async_collective_fusion=true",
    ]


def apply_tpu_overlap_flags(environ) -> bool:
    """Append tpu_overlap_flags() to environ['LIBTPU_INIT_ARGS'] (once,
    idempotent). Returns True when anything was added. Call BEFORE the
    first jax computation initializes the backend. A flag the operator
    already spelled in LIBTPU_INIT_ARGS — with ANY value, including an
    explicit `=false` opt-out — is left alone, never contradicted."""
    if environ.get("CAFFE_TPU_NO_OVERLAP_FLAGS") == "1":
        return False
    cur = environ.get("LIBTPU_INIT_ARGS", "")
    add = [f for f in tpu_overlap_flags()
           if f.split("=", 1)[0] not in cur]
    if not add:
        return False
    environ["LIBTPU_INIT_ARGS"] = (cur + " " + " ".join(add)).strip()
    return True
