"""The one place that spells the names a profiler trace of this program
carries. Two mechanisms, neither with a store or a switch of its own:

- host spans are `jax.profiler.TraceAnnotation`s, inert (0.3 us) unless a
  profiler session is on, and written on the profiler's clock;
- device scopes are `jax.named_scope`s: HLO `op_name` metadata only, the
  compiled code and its numerics do not change.

Scope grammar. A prototxt layer's scope is

    caffe.<Type>.<name>     <Type>: [A-Za-z0-9_]+, as in the prototxt
                            <name>: the layer's name, percent-encoded
                                    (urllib `quote`, nothing kept safe)

so `inception_3a/1x1` reads `caffe.Convolution.inception_3a%2F1x1`: the
text holds no `/`, `(` or `)`, the separators of an `op_name`
(`jit(step)/transpose(jvp(caffe.LRN.norm1))/mul`). `parse_scope` recovers
(type, name) from an `op_name` alone; of nested layer scopes (a layer inside
a `Pipeline` block) the innermost, which is the last, wins. Under
`jax.value_and_grad` a scope reads `jvp(<scope>)` in the forward pass and
`transpose(jvp(<scope>))` in the backward pass, which is all a reader needs
to tell them apart.

| name | kind | covers |
|---|---|---|
| `caffe.<Type>.<name>` | scope | one layer's `apply` (Net.apply_range, Pipeline blocks); the block-diffusion recipe's noise layer reads `caffe.BlockDiffusionNoise.ids`, the slice of its noisy half `caffe.Slice.noisy` |
| `moe.route` | scope | inside a dropless `MoE` layer: router product, top-k, softmax (ops/moe.py) |
| `moe.dispatch` | scope | sort of the (token, choice) pairs by expert, group sizes, gather of the rows |
| `moe.experts` | scope | the grouped matrix products over the held experts' rows and the activation between them |
| `moe.combine` | scope | weighting by the router's weights and the sum of each token's rows (a gather by the inverse permutation) |
| `moe.shared` | scope | the shared experts' gated unit, which every token passes through (no sort, no weights) |
| `moe.fallback` | scope | around `moe.dispatch` / `moe.experts` / `moe.combine` where they run on a buffer of every (token, choice) pair because the held experts' rows reached the layer's row bound; no time under it = the bounded branch ran every time |
| `solver.update` | scope | unscale, clip, LR policy, optimizer update, master-weight cast, skip-step guard |
| `solver.reduce` | scope | the bucketed gradient psums of `reduce_overlap` (parallel/reduction.py) |
| `caffe/solver/iter` | step span | one pass of `Solver.step`'s loop (`step_num` = its first iteration) |
| `caffe/solver/feed wait` | span | batch assembly, re-layout and host-to-device placement |
| `caffe/solver/train dispatch` | span | launching the train program (one step, a fused chunk, a GPipe wavefront) and its scalar arguments (`fold_in`, the iteration's cast) |
| `caffe/solver/step sync` | span | per-program sync of host-callback nets on the CPU backend |
| `caffe/solver/display sync` | span | device-to-host read of the smoothed loss at a display boundary |
| `caffe/solver/guard check` | span | read of the skip-step guard's counters |
| `caffe/solver/eval dispatch` | span | weight copy and launch of evaluation chunks |
| `caffe/solver/eval harvest` | span | device-to-host read of an evaluation pass's scores |
| `caffe/solver/snapshot settle` | span | drain before a snapshot's device-side copy |
| `caffe/solver/snapshot handoff` | span | device-side copy and hand-off to the writer thread |
| `caffe/solver/snapshot gather` | span | device-to-host gather of a snapshot (writer thread when async) |
"""

from __future__ import annotations

import re
from urllib.parse import quote, unquote

import jax

UPDATE = "solver.update"
MOE_ROUTE = "moe.route"
MOE_DISPATCH = "moe.dispatch"
MOE_EXPERTS = "moe.experts"
MOE_COMBINE = "moe.combine"
MOE_SHARED = "moe.shared"
MOE_FALLBACK = "moe.fallback"
REDUCE = "solver.reduce"
ITER = "solver/iter"
_SCOPE = re.compile(r"caffe\.([A-Za-z0-9_]+)\.([A-Za-z0-9_.~%-]*)")


def span(name: str, **stats):
    """Host span `caffe/<name>`; keyword arguments become its statistics."""
    return jax.profiler.TraceAnnotation("caffe/" + name, **stats)


def iteration(step_num: int):
    """The step span around one pass of the train loop: XProf groups by it,
    and the spans nested in it read their iteration from `step_num`."""
    return jax.profiler.StepTraceAnnotation("caffe/" + ITER,
                                            step_num=step_num)


def scope_name(type_: str, name: str) -> str:
    return f"caffe.{type_}.{quote(name, safe='')}"


def layer_scope(layer):
    """Device scope of a prototxt layer (anything with `.lp.type` and
    `.name`)."""
    return jax.named_scope(scope_name(layer.lp.type, layer.name))


def parse_scope(op_name: str) -> tuple[str, str] | None:
    """(layer type, layer name) of the innermost layer scope in an HLO
    `op_name`, or None when it holds none."""
    found = _SCOPE.findall(op_name)
    if not found:
        return None
    type_, name = found[-1]
    return type_, unquote(name)
