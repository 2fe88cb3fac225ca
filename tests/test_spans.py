"""Spans and scopes inside the program (utils/spans.py): the scope grammar
round-trips every layer of the zoo, the compiled train step's HLO carries a
forward and a backward `op_name` per layer and `solver.update` for the
update, a profiler session around `Solver.step` holds one `caffe/solver/iter`
per loop pass with its child spans nested in it, `Solver._guard` opens span
and watchdog section under one label, and `caffe train -profile DIR` leaves
an xplane. That scopes change no number is held by the existing equivalence
suites (test_multistep, test_train_guard, test_reduction, test_precision),
which pass unchanged."""

import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from caffe_mpi_tpu.proto import SolverParameter
from caffe_mpi_tpu.proto.config import NetParameter
from caffe_mpi_tpu.solver import Solver
from caffe_mpi_tpu.tools.cli import main
from caffe_mpi_tpu.utils import spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NETS = sorted(
    f for d in ("models", "examples")
    for f in glob.glob(os.path.join(ROOT, d, "**", "*.prototxt"),
                       recursive=True)
    if "solver" not in os.path.basename(f))

TOY = """
name: "toy"
layer { name: "in" type: "Input" top: "data" top: "label"
        input_param { shape { dim: 8 dim: 3 dim: 8 dim: 8 } shape { dim: 8 } } }
layer { name: "stem/conv" type: "Convolution" bottom: "data" top: "c"
        convolution_param { num_output: 8 kernel_size: 3
          weight_filler { type: "xavier" } } }
layer { name: "relu" type: "ReLU" bottom: "c" top: "c" }
layer { name: "norm" type: "LRN" bottom: "c" top: "n"
        lrn_param { local_size: 5 alpha: 0.0001 beta: 0.75 } }
layer { name: "ip" type: "InnerProduct" bottom: "n" top: "score"
        inner_product_param { num_output: 5
          weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "score" bottom: "label"
        top: "loss" }
"""


def toy_solver(extra: str = "", mesh=None) -> Solver:
    sp = SolverParameter.from_text(
        f'base_lr: 0.05 momentum: 0.9 lr_policy: "fixed" max_iter: 100 '
        f'display: 0 random_seed: 3\n{extra}')
    sp.net_param = NetParameter.from_text(TOY)
    return Solver(sp, mesh=mesh)


def toy_feeds(batch: int = 8) -> dict:
    rng = np.random.RandomState(0)
    return {"data": rng.randn(batch, 3, 8, 8).astype(np.float32),
            "label": rng.randint(0, 5, batch).astype(np.int32)}


def walk_layers(layers, outer=()):
    """(chain of enclosing layers, layer) for every layer, the blocks of
    composite (Pipeline) layers included."""
    for lp in layers:
        yield outer, lp
        pp = getattr(lp, "pipeline_param", None)
        if pp is not None and pp.layer:
            yield from walk_layers(pp.layer, outer + (lp,))


# -- the grammar -------------------------------------------------------------

@pytest.mark.parametrize("path", NETS,
                         ids=[os.path.relpath(f, ROOT) for f in NETS])
def test_scope_round_trips_every_layer(path):
    """(type, name) comes back from an `op_name` alone, forward and
    backward, for names that hold `/` and for layers nested in composite
    layers (innermost wins)."""
    npar = NetParameter.from_file(path)
    assert npar.layer
    for outer, lp in walk_layers(npar.layer):
        scope = spans.scope_name(lp.type, lp.name)
        assert not re.search(r"[/()\s;:]", scope), scope
        stack = [spans.scope_name(o.type, o.name) for o in outer] + [scope]
        fwd = "jit(step)/" + "/".join(f"jvp({s})" for s in stack) + "/mul"
        bwd = ("jit(step)/" + "/".join(f"transpose(jvp({s}))" for s in stack)
               + "/while/body/dot_general")
        for op_name in (fwd, bwd, f"jit(forward)/{'/'.join(stack)}/add"):
            assert spans.parse_scope(op_name) == (lp.type, lp.name), op_name


def test_zoo_exercises_slashes_and_composites():
    layers = [(outer, lp) for path in NETS
              for outer, lp in walk_layers(NetParameter.from_file(path).layer)]
    assert any("/" in lp.name for _, lp in layers)       # GoogLeNet
    assert any(outer for outer, _ in layers)             # Pipeline blocks
    assert len(layers) > 2000


@pytest.mark.parametrize("op_name", [
    "jit(step)/solver.update/sub", "jit(step)/jvp()/while/body/mul",
    "jit(_normal)/mul", "caffe.notalayer", ""])
def test_no_layer_scope_parses_to_none(op_name):
    assert spans.parse_scope(op_name) is None


def test_every_name_in_the_source_is_in_the_table():
    """utils/spans.py is the single spelling: each label a `_guard(...)`,
    `spans.span(...)` or `spans.phase(...)` call site uses is a row of its
    table."""
    text = open(os.path.join(ROOT, "caffe_mpi_tpu", "solver",
                             "solver.py")).read()
    labels = set(re.findall(r'_guard\("([^"]+)"\)', text))
    labels |= set(re.findall(r'spans\.span\("solver/([^"]+)"\)', text))
    assert {"feed wait", "train dispatch", "display sync", "eval dispatch",
            "snapshot handoff"} <= labels
    for label in labels:
        assert f"| `caffe/solver/{label}` |" in spans.__doc__, label
    for name in (spans.UPDATE, spans.REDUCE, "caffe/" + spans.ITER):
        assert f"| `{name}` |" in spans.__doc__
    # the start-up ledger's phases, wherever they are opened
    phases = set()
    for sub in ("net.py", "solver/solver.py", "tools/cli.py",
                "proto/config.py", "ops/pallas_call.py", "ops/moe.py"):
        text = open(os.path.join(ROOT, "caffe_mpi_tpu", sub)).read()
        phases |= set(re.findall(r'spans\.phase\(\s*"([^"]+)"', text))
        if "spans.phase(spans.KERNEL" in text:
            phases.add(spans.KERNEL)
    assert phases == {"parse", "net/build", "net/fill", "solver/build",
                      "solver/opt state", "solver/place", "solver/restore",
                      "solver/jit", "cli/feeders", "cli/first step",
                      "trace/kernel"}
    for name in phases:
        assert f"| `caffe/{name}` | phase |" in spans.__doc__, name


# -- device scopes in the compiled step --------------------------------------

def op_names(hlo_text: str) -> set[str]:
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def phases_by_layer(names) -> dict:
    out = {}
    for name in names:
        layer = spans.parse_scope(name)
        if layer is not None:
            out.setdefault(layer, set()).add(
                "backward" if "transpose(" in name else "forward")
    return out


@pytest.mark.parametrize("extra", ["", "train_guard: true",
                                   'precision: "bf16"'],
                         ids=["plain", "guard", "bf16"])
def test_step_hlo_names_every_layer_forward_and_backward(extra):
    solver = toy_solver(extra)
    try:
        names = op_names(solver.step_hlo_text(toy_feeds()))
    finally:
        solver.close()
    seen = phases_by_layer(names)
    for layer in solver.net.layers:
        if layer.params:
            assert seen.get((layer.lp.type, layer.name)) == {
                "forward", "backward"}, (layer.name, seen)
    assert ("Convolution", "stem/conv") in seen
    assert any(f"/{spans.UPDATE}/" in n for n in names)
    # nothing of the update sits under a layer, nothing of a layer under it
    assert not any(spans.UPDATE in n and spans.parse_scope(n) for n in names)


@pytest.mark.parametrize("extra", ["", "train_guard: true"],
                         ids=["plain", "guard"])
def test_jitted_programs_have_fixed_names(extra):
    """A trace names a program after its function: `jit_step`, with the
    guard's carry or without."""
    solver = toy_solver(extra)
    try:
        assert solver._build_step().__name__ == "step"
    finally:
        solver.close()


def test_bucketed_reduction_runs_under_solver_reduce():
    from caffe_mpi_tpu.parallel import MeshPlan
    solver = toy_solver("reduce_overlap: true reduce_buckets: 2",
                        mesh=MeshPlan.data_parallel(jax.devices()[:2]))
    try:
        assert solver.reduction_stats()["mode"] != "implicit"
        text = solver.step_hlo_text(toy_feeds())
    finally:
        solver.close()
    reduces = [line for line in text.splitlines() if " all-reduce(" in line]
    assert reduces
    assert all(f"/{spans.REDUCE}/" in line for line in reduces), reduces


def test_pipeline_block_layers_get_their_own_scope():
    from caffe_mpi_tpu.net import Net
    path = os.path.join(ROOT, "models", "transformer_lm",
                        "train_val_pp.prototxt")
    npar = NetParameter.from_file(path)
    inner = [lp for outer, lp in walk_layers(npar.layer) if outer]
    net = Net(npar, phase="TRAIN")
    params, state = net.init(jax.random.PRNGKey(0))
    feeds = {k: np.zeros(shape, dtype)
             for k, (shape, dtype) in net.feed_specs.items()}
    text = jax.jit(lambda p, s, f: net.apply(
        p, s, f, train=True, rng=jax.random.PRNGKey(0))[2]).lower(
            params, state, feeds).as_text(debug_info=True)
    found = {spans.parse_scope(n) for n in re.findall(r'loc\("([^"]*)"', text)}
    assert {(lp.type, lp.name) for lp in inner if lp.type != "Dropout"} \
        <= found


# -- host spans --------------------------------------------------------------

def host_spans(trace_dir) -> list:
    """(name, start, end, statistics) of the program's spans, one list per
    host thread."""
    (path,) = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    threads = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                       dict(e.stats)) for e in line.events
                      if e.name.startswith("caffe/")]
            if events:
                threads.append(events)
    return threads


def profiled(fn, trace_dir):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return host_spans(trace_dir)


def test_profiled_step_holds_one_iter_span_per_pass(tmp_path):
    solver = toy_solver("display: 2\n")
    feeds = toy_feeds()
    try:
        solver.step(1, lambda it: feeds)   # compile outside the session
        (events,) = profiled(lambda: solver.step(3, lambda it: feeds),
                             tmp_path)
    finally:
        solver.close()
    iters = [e for e in events if e[0] == "caffe/solver/iter"]
    assert [e[3]["step_num"] for e in iters] == [1, 2, 3]
    for child in ("caffe/solver/feed wait", "caffe/solver/train dispatch"):
        for _, start, end, _ in iters:
            inside = [e for e in events
                      if e[0] == child and start <= e[1] and e[2] <= end]
            assert len(inside) == 1, (child, events)
    # iteration 2 is a display boundary: its sync is a span too
    assert sum(e[0] == "caffe/solver/display sync" for e in events) == 1
    assert all(e[0] == "caffe/solver/iter" or any(
        s <= e[1] and e[2] <= t for _, s, t, _ in iters) for e in events)


def test_guard_opens_span_and_watchdog_section_under_one_label(tmp_path):
    solver = toy_solver("watchdog_deadline: 600")
    try:
        solver._ensure_watchdog()
        wd = solver._watchdog
        assert wd is not None

        def inside():
            with solver._guard("feed wait"):
                assert [label for label, _ in wd._open.values()] \
                    == ["feed wait"]
            assert not wd._open
        (events,) = profiled(inside, tmp_path)
    finally:
        solver.close()
    assert [e[0] for e in events] == ["caffe/solver/feed wait"]


def test_inert_span_is_cheap():
    """No profiler session: a span is one small C++ object, no store of
    its own. The bound is loose (CI noise); the measured cost is in
    PERF.md."""
    import time
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        with spans.span("solver/feed wait"):
            pass
    assert (time.perf_counter() - t0) / n < 20e-6


def test_compile_cache_keys_on_scope_names():
    """By jax's default the persistent cache ignores metadata: a step
    compiled under one scope name and requested under another would be
    served, and traced, with the old name."""
    from caffe_mpi_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key is True


# -- the operator's switch ---------------------------------------------------

@pytest.fixture
def solver_file(tmp_path):
    (tmp_path / "net.prototxt").write_text(TOY)
    path = tmp_path / "solver.prototxt"
    path.write_text(f'net: "{tmp_path}/net.prototxt"\nbase_lr: 0.05\n'
                    f'lr_policy: "fixed" max_iter: 10 display: 3\n'
                    f'snapshot_prefix: "{tmp_path}/snap"\n')
    return str(path)


def test_train_profile_traces_the_second_display_interval(
        solver_file, tmp_path, capsys):
    trace_dir = tmp_path / "trace"
    assert main(["train", "-solver", solver_file, "-synthetic",
                 "-profile", str(trace_dir)]) == 0
    assert f"profiler trace written to {trace_dir}" in capsys.readouterr().out
    (events,) = host_spans(trace_dir)
    steps = [e[3]["step_num"] for e in events if e[0] == "caffe/solver/iter"]
    assert steps == [3, 4, 5]       # iterations [D, 2D), D = display = 3


def test_short_run_traces_its_second_half(solver_file, tmp_path):
    trace_dir = tmp_path / "trace"
    assert main(["train", "-solver", solver_file, "-synthetic", "-max_iter",
                 "4", "-profile", str(trace_dir)]) == 0
    (events,) = host_spans(trace_dir)
    assert [e[3]["step_num"] for e in events
            if e[0] == "caffe/solver/iter"] == [2, 3]


@pytest.mark.parametrize("command", ["test", "serve", "device_query"])
def test_profile_is_refused_where_nothing_reads_it(command, capsys):
    with pytest.raises(SystemExit) as exit_:
        main([command, "-profile", "/nonexistent"])
    assert exit_.value.code == 2
    assert "-profile is read by train and time" in capsys.readouterr().err


# -- scopes inside a dropless expert layer (ops/moe.py) -----------------------

MOE_SCOPES = [spans.MOE_ROUTE, spans.MOE_DISPATCH, spans.MOE_EXPERTS,
              spans.MOE_COMBINE, spans.MOE_SHARED]


@pytest.fixture(scope="module")
def expert_layer_op_names():
    """`op_name`s of the compiled gradient of a dropless layer with a
    shared expert (sigmoid scoring, a selection bias, SiLU gates)."""
    import jax
    import jax.numpy as jnp
    from caffe_mpi_tpu.ops.moe import moe_dropless
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    shapes = {"gate": (16, 8), "select_bias": (8,), "w1": (2, 16, 8),
              "w3": (2, 16, 8), "w2": (2, 8, 16), "shared_w1": (16, 8),
              "shared_w3": (16, 8), "shared_w2": (8, 16)}
    params = {k: jax.random.normal(key, shape)
              for key, (k, shape) in zip(ks, shapes.items())}
    x = jax.random.normal(ks[-1], (12, 16))

    def loss(params, x):
        y, _ = moe_dropless(params, x, x, top_k=2, scoring="sigmoid",
                            scale=2.5, activation="silu")
        return jnp.sum(y * y)
    return op_names(jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text())


@pytest.mark.parametrize("scope", MOE_SCOPES)
def test_expert_layer_scopes_are_in_the_table_and_in_the_step(
        scope, expert_layer_op_names):
    assert f"| `{scope}` | scope |" in spans.__doc__
    carrying = [n for n in expert_layer_op_names if f"({scope})" in n]
    assert {"transpose(" in n for n in carrying} == {False, True}, scope
    # a scope of its own: no operation sits under two of them
    assert not any(other in n for n in carrying
                   for other in MOE_SCOPES if other != scope)


@pytest.mark.parametrize("held", [2, 8], ids=["a-share", "every-expert"])
def test_fallback_scope_opens_inside_a_sharing_layer_only(held, monkeypatch):
    """`moe.fallback` wraps the other scopes of the branch that runs on a
    buffer of every (token, choice) pair. A layer that holds a share of
    the experts carries it, forward and backward, always under its own
    layer scope; a layer that holds them all has one buffer and no such
    branch."""
    import jax.numpy as jnp
    from caffe_mpi_tpu.net import Net
    from caffe_mpi_tpu.ops import moe as moe_ops
    assert f"| `{spans.MOE_FALLBACK}` | scope |" in spans.__doc__
    monkeypatch.setattr(moe_ops, "ROW_TILE", 8)   # 64 pairs: a bound of 24
    net = Net(NetParameter.from_text(f"""
        layer {{ name: "in" type: "Input" top: "x"
                 input_param {{ shape {{ dim: 1 dim: 32 dim: 16 }} }} }}
        layer {{ name: "blk/moe" type: "MoE" bottom: "x" top: "y"
                 moe_param {{ num_experts: 8 hidden_dim: 8 top_k: 2
                              dropless: true experts_held: {held} }} }}
        """), phase="TRAIN")
    params, state = net.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 16))

    def loss(params, x):
        blobs, _, _ = net.apply(params, state, {"x": x}, train=True,
                                rng=None)
        return jnp.sum(blobs["y"] ** 2)
    names = op_names(jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text())
    carrying = [n for n in names if spans.MOE_FALLBACK in n]
    if held == 8:
        assert not carrying
        return
    assert {"transpose(" in n for n in carrying} == {False, True}
    for name in carrying:
        assert spans.parse_scope(name) == ("MoE", "blk/moe"), name
    inner = {scope for scope in MOE_SCOPES for n in carrying
             if f"{spans.MOE_FALLBACK})/{scope}" in n
             or f"{spans.MOE_FALLBACK}/{scope}" in n}
    assert inner == {spans.MOE_DISPATCH, spans.MOE_EXPERTS,
                     spans.MOE_COMBINE}
