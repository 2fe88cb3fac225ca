"""The `train_mhc_lm` driver: one training job of the language model whose
residual is manifold-constrained hyper-connections (`configs/
xing4_29b_a4b.json`), dispatched as `caffe train -solver <recipe>
-synthetic -precision bf16` dispatches it.

It is `drivers/train_mla_lm.py`'s `run`, the same function, with
`reference/xing_ref.py` as the yardstick in place of joyai's: its globals
are that module's with the reference, the feeds (tokens and label, no MTP
label), the expert layers whose rows are counted (the leading dense block
has none) and the logits check (one head) replaced (`_ours`); nothing there
is edited. `correct` is that driver's: (a) the logits of the timed `Net`
within a relative RMS of the reference computed in blocks, and the gradient
of its loss, leaf by leaf and as a whole, within a relative norm of
`jax.grad` of the reference's blocked loss; (b) every loss finite, and the
fixed batch's loss under a share of its first value by a stated iteration;
(c) no program built inside the window; (d) as many Mosaic calls a step as
the configuration states. The record also carries `xing_sizes`, which the
hyper-connection readers (`layer_metrics/mhc_*`) read.

`python3 benchmarks/drivers/train_mhc_lm.py --controls [--seed n]
[--rehearse] [--only fault ...]` puts the reference with one fault planted
in the program's place and runs the same two comparisons against the same
limits (`controls`): each must come out not correct by at least one limit,
and the reference with operands rounded to bf16, the program's own
precision, correct.
"""

from __future__ import annotations

import importlib.util
import json
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

BENCH = Path(__file__).resolve().parent.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from reference import xing_ref  # noqa: E402


def _load(path: Path):
    name = f"bench_{path.parent.name}_{path.stem}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


mla = _load(BENCH / "drivers" / "train_mla_lm.py")
lm = mla.lm
train = lm.train


def rows_blobs(sz: xing_ref.Sizes) -> list[str]:
    return [f"blk{l}/moe_rows" for l in range(sz.dense_layers, sz.layers)]


def reference_hidden(params, feeds: dict, sz, q_block: int, **how):
    """What the head reads: (tokens, C)."""
    return xing_ref.hidden(xing_ref.from_net(params, sz), feeds["tokens"],
                           sz, q_block, **how).reshape(-1, sz.hidden)


def reference_grads(params, feeds: dict, sz, spec: dict, **how):
    """`jax.grad` of the reference's blocked loss, in the program's blob
    layouts (`from_net` is linear, so it carries gradients back)."""
    return jax.grad(lambda p: xing_ref.loss_blocked(
        xing_ref.from_net(p, sz), feeds["tokens"], feeds["label"], sz,
        spec["q_block"], spec["vocab_block"], **how))(params)


def logits_check(cell: dict, job, feeds: dict, sz):
    """`train_mla_lm.logits_check` with one head: the relative RMS distance
    of the timed net's logits from the reference's on the timed batch and
    the solver's fresh weights, the rows each held expert received, and the
    function that counts them again on later weights."""
    from caffe_mpi_tpu.net import Net

    spec, _ = lm.check_specs(cell)
    net = Net(job.npar, phase="TRAIN", precision=job.precision)
    params, state = job.solver.params, job.solver.net_state
    counted = rows_blobs(sz)

    @jax.jit
    def system(params, state, feeds):
        blobs, _, _ = net.apply(params, state, feeds, train=True,
                                rng=jax.random.PRNGKey(0))
        return (blobs["logits"].reshape(-1, sz.vocab),
                [blobs[b] for b in counted])

    as_lists = lambda rows: [np.asarray(r, np.float64).tolist()
                             for r in jax.device_get(rows)]
    count_rows = lambda params: as_lists(system(params, state, feeds)[1])
    got, rows = system(params, state, feeds)
    x = jax.jit(lambda p, f: reference_hidden(p, f, sz, spec["q_block"]))(
        params, feeds)
    num, den, finite = jax.device_get(jax.jit(
        lambda p, x, got: lm.logits_distance(
            p["logits"]["weight"], x, got, spec["vocab_block"]))(
                params, x, got))
    seq = feeds["tokens"].shape[1]
    return count_rows, {
        "blob": "logits", "phase": "TRAIN",
        "sequences": got.shape[0] // seq, "seq_len": seq,
        **lm.logits_verdict(num, den, finite, got.size,
                            spec["rel_rms_max"][job.precision]),
        "routed_rows": as_lists(rows)}


def held_together(leaves: dict) -> dict:
    """The gradient check's leaves with each hyper-connection layer's three
    blobs (phi, bias, alpha) as one: bias and alpha receive sums over the
    tokens of terms that nearly cancel (streams that start as copies of
    one another, and a change of H_res that keeps its rows' sums moves
    nothing), so in bf16 their own relative error reads near 1 whatever
    the program does (0.95 on one seed on a v5e); phi's carries the
    layer."""
    hc = {k.rsplit("/", 1)[0] for k in leaves if k.endswith("/phi")}
    out = {}
    for k, (num, den) in leaves.items():
        key = k.rsplit("/", 1)[0] if k.rsplit("/", 1)[0] in hc else k
        n0, d0 = out.get(key, (0.0, 0.0))
        out[key] = (n0 + num, d0 + den)
    return out


def grads_verdict(leaves: dict, limits: dict, precision: str) -> dict:
    return lm.grads_verdict(held_together(leaves), limits, precision)


_scope = {**vars(mla), "joyai_ref": xing_ref, "FEEDS": {"tokens", "label"},
          "make_tokens": lm.make_tokens, "rows_blobs": rows_blobs,
          "reference_hidden": reference_hidden,
          "reference_grads": reference_grads, "logits_check": logits_check,
          "lm": types.SimpleNamespace(**{**vars(lm),
                                         "grads_verdict": grads_verdict})}


def _ours(fn):
    """`fn` of `train_mla_lm` reading this file's reference, feeds, counted
    layers and logits check where it reads joyai's (`_scope`)."""
    _scope[fn.__name__] = types.FunctionType(
        fn.__code__, _scope, fn.__name__, fn.__defaults__, fn.__closure__)
    return _scope[fn.__name__]


fresh_net = _ours(mla.fresh_net)
grads_check = _ours(mla.grads_check)
_run = _ours(mla.run)


def run(cell: dict, **kw) -> dict:
    """Run the cell once; the arguments and the result are `drivers/
    train.py`'s."""
    result = _run(cell, **kw)
    config, preset = cell["config"], cell["preset"]
    result["record"]["xing_sizes"] = xing_ref.sizes_record(
        xing_ref.sizes_from_config(config, preset))
    return result


# -- controls: the reference with one fault planted, in the program's place --

def controls(cell: dict, seed: int, say, only=()) -> bool:
    """The set-up's two comparisons, against the same limits, with the
    reference under each planted fault where the program stands: operands
    rounded to bf16 (sound: the program's own precision) and to an 8-bit
    float (a compile each), and each of `xing_ref.FAULTS` (one compile for
    all: the faults are data). Every fault's gradient is read, also where
    its logits were refused already, so that each gradient limit has a
    reading above it; the sound control is held to the logits alone (the
    program's own seeds are the gradient's sound readings). True if every
    fault came out not correct by at least one limit and the sound control
    correct."""
    cell = lm.with_preset(cell)
    config, traffic, preset = cell["config"], cell["traffic"], cell["preset"]
    sz = xing_ref.sizes_from_config(config, preset)
    net, batch, seq = fresh_net(cell, "f32")
    key = jax.random.PRNGKey(seed)
    params, _ = net.init(key)
    feeds = lm.make_tokens(jax.random.fold_in(key, 1), batch, seq, sz.vocab)
    spec, gspec = lm.check_specs(cell)
    precision = traffic["precision"]
    operands = {"operands_bf16": jnp.bfloat16,
                "operands_f8_e4m3": jnp.float8_e4m3fn}
    chosen = [k for k in (*operands, *xing_ref.FAULTS)
              if not only or k in only]
    planted = lambda name: {k: jnp.float32(k == name)
                            for k in xing_ref.FAULTS}

    hidden = jax.jit(lambda p, f, fault: reference_hidden(
        p, f, sz, spec["q_block"], fault=fault))
    logits = jax.jit(lambda p, x: xing_ref.logits_block(
        {"head": jnp.asarray(p["logits"]["weight"], jnp.float32).T}, x, 0,
        sz.vocab))
    distance = jax.jit(lambda p, x, got: lm.logits_distance(
        p["logits"]["weight"], x, got, spec["vocab_block"]))
    x = hidden(params, feeds, planted(None))
    refused = {}
    for name in chosen:
        if name in operands:
            dt = operands[name]
            got = jax.jit(lambda p, f: xing_ref.logits_block(
                {"head": jnp.asarray(p["logits"]["weight"], jnp.float32).T},
                reference_hidden(p, f, sz, spec["q_block"],
                                 operand_dtype=dt), 0, sz.vocab, dt))(
                    params, feeds)
        else:
            got = logits(params, hidden(params, feeds, planted(name)))
        verdict = lm.logits_verdict(
            *jax.device_get(distance(params, x, got)), got.size,
            spec["rel_rms_max"][precision])
        refused[name] = not verdict["ok"]
        say(control="logits", fault=name, sound=name == "operands_bf16",
            correct=verdict["ok"], **verdict)
    del x, got

    grad = jax.jit(lambda p, f, fault: reference_grads(p, f, sz, gspec,
                                                       fault=fault))
    frozen = lm.frozen_leaves(net)
    distance = jax.jit(lambda have, want: lm.leaf_distances(have, want,
                                                            frozen))
    want = grad(params, feeds, planted(None))
    for name in chosen:
        if name == "operands_bf16":
            continue
        if name in operands:
            dt = operands[name]
            have = jax.jit(lambda p, f: reference_grads(
                p, f, sz, gspec, operand_dtype=dt))(params, feeds)
        else:
            have = grad(params, feeds, planted(name))
        verdict = grads_verdict(jax.device_get(distance(have, want)), gspec,
                                precision)
        del have
        refused[name] |= not verdict["ok"]
        say(control="grads", fault=name, sound=False, correct=verdict["ok"],
            **verdict)
    as_expected = all(refused[name] != (name == "operands_bf16")
                      for name in chosen)
    say(controls_as_expected=bool(as_expected),
        not_refused=[k for k in chosen
                     if k != "operands_bf16" and not refused[k]])
    return bool(as_expected)


def main(argv: list[str]) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=controls.__doc__)
    ap.add_argument("--controls", action="store_true", required=True)
    ap.add_argument("--workload", default="xing4_bf16_s4k_mhc4_ep8share")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--only", nargs="*", default=())
    args = ap.parse_args(argv)
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    harness = _load(BENCH / "run.py")
    cell = harness.load_cell(args.workload, args.rehearse)
    say = lambda **fields: print(json.dumps(fields), flush=True)
    return 0 if controls(cell, args.seed, say, args.only) else 1


if __name__ == "__main__":
    sys.path.insert(0, str(BENCH.parent))
    sys.exit(main(sys.argv[1:]))
