"""The one place that spells the names this program's traces carry. Two
mechanisms live in a profiler's trace and keep nothing themselves:

- host spans are `jax.profiler.TraceAnnotation`s, inert (0.3 us) unless a
  profiler session is on, and written on the profiler's clock;
- device scopes are `jax.named_scope`s: HLO `op_name` metadata only, the
  compiled code and its numerics do not change.

A third covers start-up, which no profiler session does (`caffe train
-profile` and the benchmark both start theirs after the first step): the
**start-up ledger**, `ledger`, one bounded store a process, always on and
written only while programs are being built. It holds the phases opened by
`phase` (at most `PHASE_CAP` records, the rest counted in
`ledger.dropped`) and the Python seconds inside layer applies by layer type
(`layer_scope`: under `jit` that is trace time). Its third part, one row a
program jax traced, lowered and built, is kept by the listeners of
utils/compile_cache.py (`programs`). Both stamp `time.perf_counter()`.
`ledger.table()` prints it; `caffe train` logs that once, after its first
`solver.step` call (docs/profiling.md, "Where start-up time goes"). A phase is
for work done once a program or once a run: inside the loop of
`Solver.step` `span` stays the only tool (tests/test_startup_ledger.py
holds that).

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
| `cca.project` | scope | inside an `Attention` layer with `cca`: the products from the bottom (queries and keys down into the latent, the two value halves) (layers/sequence.py `_cca_qkv`) |
| `cca.mix` | scope | what that attention adds to a grouped head: the shifts along the sequence, the convolution a channel and the one a head, the q-k mean, the normalisation under the temperature, the rotary turn over part of the head |
| `cca.out` | scope | the output product, latent back up to the channels |
| `ssm.project` | scope | inside a `Mamba2` layer (layers/sequence.py): the one input product to z, x B C and dt |
| `ssm.conv` | scope | the depthwise causal convolution over x B C (shifted sums), its bias and SiLU, the split |
| `ssm.scan` | scope | softplus, the decays and the selective recurrence in chunks (ops/ssd.py): four products and the carried states; its backward pass, written by hand, opens the same scope and builds the chunks' matrices again from the kept running sums |
| `ssm.gate` | scope | y * silu(z), the RMS norm a group, the learned scale |
| `ssm.out` | scope | the output product |
| `mhc.sinkhorn` | scope | inside a `HyperConnectionPre` layer (layers/hyper_connection.py): the Sinkhorn-Knopp iterations that make a token's residual mix doubly stochastic, rows then columns divided by their sums, float32 |
| `moe.route` | scope | inside a dropless `MoE` layer: router product (none where the layer is handed logits), top-k, softmax (ops/moe.py) |
| `moe.dispatch` | scope | sort of the (token, choice) pairs by expert, group sizes, gather of the rows |
| `moe.experts` | scope | the grouped matrix products over the held experts' rows and the activation between them |
| `moe.combine` | scope | weighting by the router's weights and the sum of each token's rows (a gather by the inverse permutation) |
| `moe.shared` | scope | the shared experts' gated unit, which every token passes through (no sort, no weights) |
| `moe.fallback` | scope | around `moe.dispatch` / `moe.experts` / `moe.combine` where they run on a buffer of every (token, choice) pair because the held experts' rows reached the layer's row bound; no time under it = the bounded branch ran every time |
| `solver.update` | scope | unscale, clip, LR policy, optimizer update, master-weight cast, skip-step guard |
| `solver.reduce` | scope | the bucketed gradient psums of `reduce_overlap` (parallel/reduction.py) |
| `caffe/solver/iter` | step span | one pass of `Solver.step`'s loop (`step_num` = its first iteration) |
| `caffe/solver/feed wait` | span | batch assembly, re-layout and host-to-device placement |
| `caffe/solver/train dispatch` | span | launching the train program: one step, and nothing else (its key is folded inside it); a GPipe wavefront with its `fold_in` |
| `caffe/solver/step sync` | span | per-program sync of host-callback nets on the CPU backend |
| `caffe/solver/display sync` | span | device-to-host read of the smoothed loss at a display boundary |
| `caffe/solver/guard check` | span | read of the skip-step guard's counters |
| `caffe/solver/eval dispatch` | span | weight copy and launch of evaluation chunks |
| `caffe/solver/eval harvest` | span | device-to-host read of an evaluation pass's scores |
| `caffe/solver/snapshot settle` | span | drain before a snapshot's device-side copy |
| `caffe/solver/snapshot handoff` | span | device-side copy and hand-off to the writer thread |
| `caffe/solver/snapshot gather` | span | device-to-host gather of a snapshot (writer thread when async) |
| `caffe/parse` | phase | one prototxt text into its message (`Message.from_text`, so `from_file` too); statistics `message`, `bytes` |
| `caffe/net/build` | phase | `Net.__init__`: filter, layer set-up, shape inference; `phase`, `layers` |
| `caffe/net/fill` | phase | `Net.init`: the fillers, **host seconds** (they dispatch asynchronously; the programs they built are rows of `programs` stamped inside it); `layers`, `parameters` |
| `caffe/solver/build` | phase | the whole of `Solver.__init__` |
| `caffe/solver/opt state` | phase | the optimizer's zeroed slots, inside `solver/build` |
| `caffe/solver/place` | phase | `Solver._place_params_opt` and the state's replication: mesh replicate / shardings, stage placement |
| `caffe/solver/restore` | phase | `Solver.restore`, `restore_native`, `load_weights`: a snapshot or weights read and placed |
| `caffe/solver/jit` | phase | `Solver._build_step`: building the step's function and its `jax.jit` wrapper (tracing waits for the first call) |
| `caffe/cli/feeders` | phase | `cmd_train`: the train and test feeders (or the synthetic batch) |
| `caffe/cli/first step` | phase | `cmd_train`: its first `solver.step` call, which traces, lowers and builds the step |
| `caffe/trace/kernel` | phase | ops/pallas_call.py `call`, ops/moe.py: one trace of a Pallas kernel's caller; `kernel`, and `branch`, the arm of `lax.platform_dependent` being traced (`cpu`: the interpreter, `default`: Mosaic). Trace time only: a compiled step never reaches it |
"""

from __future__ import annotations

import re
import sys
import threading
import time
from urllib.parse import quote, unquote

from . import compile_cache

UPDATE = "solver.update"
CCA_PROJECT = "cca.project"
CCA_MIX = "cca.mix"
CCA_OUT = "cca.out"
SSM_PROJECT = "ssm.project"
SSM_CONV = "ssm.conv"
SSM_SCAN = "ssm.scan"
SSM_GATE = "ssm.gate"
SSM_OUT = "ssm.out"
MHC_SINKHORN = "mhc.sinkhorn"
MOE_ROUTE = "moe.route"
MOE_DISPATCH = "moe.dispatch"
MOE_EXPERTS = "moe.experts"
MOE_COMBINE = "moe.combine"
MOE_SHARED = "moe.shared"
MOE_FALLBACK = "moe.fallback"
REDUCE = "solver.reduce"
ITER = "solver/iter"
KERNEL = "trace/kernel"
STEP_PROGRAM = "step"   # `Solver`'s, by its row's name
PHASE_CAP = 4096
_SCOPE = re.compile(r"caffe\.([A-Za-z0-9_]+)\.([A-Za-z0-9_.~%-]*)")
_clock = time.perf_counter
_local = threading.local()   # a thread's phase depth and open layer scopes


def _jax():
    """jax where the process has imported it, else None: the jax-free tools
    (tpulint, `summarize`, the launchers that leave the chip to their
    children) parse prototxts too, and a `parse` phase must not pull jax in
    for them. Every other caller here has imported jax long before."""
    return sys.modules.get("jax")


def span(name: str, **stats):
    """Host span `caffe/<name>`; keyword arguments become its statistics."""
    return _jax().profiler.TraceAnnotation("caffe/" + name, **stats)


def iteration(step_num: int):
    """The step span around one pass of the train loop: XProf groups by it,
    and the spans nested in it read their iteration from `step_num`."""
    return _jax().profiler.StepTraceAnnotation("caffe/" + ITER,
                                               step_num=step_num)


class PhaseRecord:
    """One phase of the ledger: seconds on `time.perf_counter`, `depth` the
    number of phases open around it on its thread, `end` None while open."""
    __slots__ = ("name", "start", "end", "depth", "stats")

    def __init__(self, name: str, stats: dict):
        self.name, self.stats = name, stats
        self.start = self.end = None
        self.depth = 0

    @property
    def seconds(self) -> float:
        return 0.0 if self.end is None else self.end - self.start


class phase:
    """Start-up phase `caffe/<name>`: the same annotation as `span` (a
    profiler session that does cover start-up shows it beside the device
    planes) and a record in `ledger.phases`, in order of opening. `with
    phase(...) as record` hands the record out, so that a statistic known
    only at the end can be added to `record.stats`. Never inside the loop
    of `Solver.step`."""
    __slots__ = ("record", "annotation")

    def __init__(self, name: str, **stats):
        jax = _jax()
        self.annotation = None if jax is None else \
            jax.profiler.TraceAnnotation("caffe/" + name, **stats)
        self.record = PhaseRecord(name, stats)

    def __enter__(self) -> PhaseRecord:
        if self.annotation is not None:
            self.annotation.__enter__()
        record = self.record
        record.depth = getattr(_local, "depth", 0)
        _local.depth = record.depth + 1
        if len(ledger.phases) < PHASE_CAP:
            ledger.phases.append(record)
        else:
            ledger.dropped += 1
        record.start = _clock()
        return record

    def __exit__(self, *exc):
        self.record.end = _clock()
        _local.depth -= 1
        if self.annotation is not None:
            self.annotation.__exit__(*exc)


def scope_name(type_: str, name: str) -> str:
    return f"caffe.{type_}.{quote(name, safe='')}"


class layer_scope:
    """Device scope of a prototxt layer (anything with `.lp.type` and
    `.name`). The body's host seconds, less those of layer scopes nested in
    it (a `Pipeline` block's), go to `ledger.apply_s[<Type>]`: two clock
    reads a layer and trace, and under `jit` the body runs only then."""
    __slots__ = ("type_", "scope", "start")

    def __init__(self, layer):
        self.type_ = layer.lp.type
        self.scope = _jax().named_scope(scope_name(self.type_, layer.name))

    def __enter__(self):
        self.scope.__enter__()
        if not hasattr(_local, "nested"):
            _local.nested = []
        _local.nested.append(0.0)   # seconds of the scopes inside this one
        self.start = _clock()

    def __exit__(self, *exc):
        elapsed = _clock() - self.start
        own = elapsed - _local.nested.pop()
        if _local.nested:
            _local.nested[-1] += elapsed
        ledger.apply_s[self.type_] = ledger.apply_s.get(self.type_, 0.0) + own
        return self.scope.__exit__(*exc)


def parse_scope(op_name: str) -> tuple[str, str] | None:
    """(layer type, layer name) of the innermost layer scope in an HLO
    `op_name`, or None when it holds none."""
    found = _SCOPE.findall(op_name)
    if not found:
        return None
    type_, name = found[-1]
    return type_, unquote(name)


class Ledger:
    """The start-up ledger's own two parts (the module's docstring): the
    phases and the layer types' Python seconds. The programs jax built are
    `compile_cache.programs`; `table` and `snapshot` read both."""

    def __init__(self):
        self.phases: list[PhaseRecord] = []
        self.dropped = 0
        self.apply_s: dict[str, float] = {}

    def outermost(self, *names: str) -> list[PhaseRecord]:
        """The closed phases of these names that lie inside no other phase
        of these names: what a sum over them may count once."""
        picked = sorted((r for r in self.phases
                         if r.name in names and r.end is not None),
                        key=lambda r: (r.start, -r.end))
        out, covered = [], float("-inf")
        for r in picked:
            if r.end > covered:
                out.append(r)
                covered = r.end
        return out

    def seconds(self, *names: str) -> float:
        return sum(r.seconds for r in self.outermost(*names))

    def kernels(self) -> dict[str, dict]:
        """`trace/kernel` phases by kernel: seconds, count, branches."""
        out: dict[str, dict] = {}
        for r in self.outermost(KERNEL):
            row = out.setdefault(r.stats.get("kernel", "?"),
                                 {"s": 0.0, "n": 0, "branches": {}})
            row["s"] += r.seconds
            row["n"] += 1
            branch = r.stats.get("branch", "?")
            row["branches"][branch] = row["branches"].get(branch, 0) + 1
        return out

    def snapshot(self) -> dict:
        """The whole ledger as plain JSON values, for a reader outside the
        program (benchmarks/startup_reduce.py)."""
        return {"phases": [[r.name, r.start, r.end, r.depth, dict(r.stats)]
                           for r in self.phases if r.end is not None],
                "phases_dropped": self.dropped,
                "apply_s": dict(self.apply_s),
                "programs": compile_cache.programs.snapshot()}

    def table(self) -> str:
        """The one formatter: where start-up time went, in host seconds,
        for an operator without a profiler."""
        programs = compile_cache.programs
        lines = ["Where start-up time went (host seconds; "
                 "docs/profiling.md):"]

        def line(label: str, seconds: float, note: str = "") -> None:
            lines.append(f"  {label:<16}{seconds:9.3f}  {note}".rstrip())

        def stat(name: str, key: str) -> int:
            return sum(r.stats.get(key, 0) for r in self.outermost(name))

        def largest(seconds_by_name: dict, n: int = 3) -> str:
            top = sorted(seconds_by_name.items(), key=lambda kv: -kv[1])[:n]
            return ", ".join(f"{name} {s:.3f}" for name, s in top)

        line("parse", self.seconds("parse"),
             f"{len(self.outermost('parse'))} texts, "
             f"{stat('parse', 'bytes'):,} bytes")
        line("net/build", self.seconds("net/build"),
             f"{len(self.outermost('net/build'))} nets, "
             f"{stat('net/build', 'layers'):,} layers")
        built = sum(programs.built_between(r.start, r.end)
                    for r in self.outermost("net/fill"))
        line("net/fill", self.seconds("net/fill"),
             f"{stat('net/fill', 'parameters'):,} parameters, {built} "
             f"programs built inside it; the fillers dispatch "
             f"asynchronously")
        line("solver/build", self.seconds("solver/build"),
             f"its net/build and net/fill included; opt state "
             f"{self.seconds('solver/opt state'):.3f}, place "
             f"{self.seconds('solver/place'):.3f}")
        for name in ("solver/restore", "cli/feeders", "cli/first step"):
            if self.outermost(name):
                line(name, self.seconds(name))
        row = programs.rows.get(STEP_PROGRAM)
        if row is not None:
            how = (f"{row.retrieval_s:.3f} of it the load from the "
                   f"cache" if row.hits else "the compile")
            line(f"program `{STEP_PROGRAM}`",
                 row.trace_s + row.lower_s + row.backend_s,
                 f"trace {row.trace_s:.3f}, lower {row.lower_s:.3f}, "
                 f"backend {row.backend_s:.3f} ({how})")
        sums = programs.sums()
        line("programs", sums["trace_s"] + sums["lower_s"]
             + sums["backend_s"],
             f"{sums['built']} built, {sums['hits']} of them from the "
             f"cache: trace {sums['trace_s']:.3f}, lower "
             f"{sums['lower_s']:.3f}, backend {sums['backend_s']:.3f}")
        line("layer Python", sum(self.apply_s.values()),
             largest(self.apply_s))
        kernels = self.kernels()
        line("kernel traces", sum(k["s"] for k in kernels.values()),
             largest({f"{name} x{k['n']}": k["s"]
                      for name, k in kernels.items()}))
        if self.dropped or programs.dropped:
            lines.append(f"  dropped: {self.dropped} phases, "
                         f"{programs.dropped} build events past the caps")
        return "\n".join(lines)


ledger = Ledger()
