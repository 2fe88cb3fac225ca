"""The `train_lm` driver: one language-model training job, dispatched as
`caffe train -solver <recipe> -synthetic -precision bf16` dispatches it.

It is `drivers/train.py` with a token feed and token-level checks: the
`Solver` is built by that driver's `build_job`, warmed up and timed by its
`measure`, and counted by its `CompileCounter` and `peak_device_bytes`
(loaded from the file, nothing there is edited). What differs:

- the batch is token ids, uniform over the configuration's vocabulary slice
  from `--seed`, and labels the next token (the last wraps to the first), as
  int32 on the device: a bf16 cast would hold ids exactly only up to 256;
- a sample is one sequence. The sequence length is the recipe's (the
  harness rewrites only the batch dimension of an `Input`), and the traffic
  file's `seq_len` must agree with it;
- `correct` = (a) the logits of the timed `Net` at the timed sizes and
  precision, on the solver's fresh weights and the timed batch, within a
  relative RMS of `reference/lm_ref.py` computed in blocks, and the
  gradient of the timed `Net`'s loss on the same weights and batch (what
  the first step applies: the flash kernels' and the grouped products'
  backward passes, the dispatch's transposes), leaf by leaf, within a
  relative norm of `jax.grad` of the reference's blocked loss; (b) every
  loss finite, and the fixed batch's loss under a stated share of
  ln(vocabulary) by a stated iteration; (c) no program built inside the
  window; (d) as many Mosaic calls a step as the configuration states;
- the set-up check also reads, from the same forward pass, the rows each
  held expert received (the expert layers' second top): a counter for
  `moe_rows_max_over_mean` and the work `moe_experts_roofline` divides by.
  It is read once more after the window, on the weights the window left,
  and never inside it. The recipe freezes the routers, so the two
  readings differ only by what the other weights' training moves.

`python3 benchmarks/drivers/train_lm.py --controls --workload <cell> --seed
<n> [--rehearse]` puts the reference with one fault planted (operands
rounded to an 8-bit float, the window dropped, rotary left out, the
router's weights renormalised over the held experts, the loss over half
the positions) in the program's place and runs the same two comparisons
against the same limits: each must come out not correct, and the
reference with operands rounded to bf16, which is the program's own
precision, correct. That is where the limits' wrong readings come from.

A rehearsal (`--rehearse`) swaps in the tiny recipe the configuration's
`rehearse` preset names, which `models/generate_models.py` emits beside the
real one, and runs the same control flow on the CPU.
"""

from __future__ import annotations

import copy
import dataclasses
import importlib.util
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

BENCH = Path(__file__).resolve().parent.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402
from reference import lm_ref  # noqa: E402


def _load(path: Path):
    name = f"bench_{path.parent.name}_{path.stem}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


train = _load(BENCH / "drivers" / "train.py")


def with_preset(cell: dict) -> dict:
    """The cell a rehearsal builds: the preset's tiny recipe in place of
    the configuration's. The cell on the chip is returned as it is."""
    if "solver" not in cell["preset"]:
        return cell
    cell = copy.deepcopy(cell)
    cell["config"]["recipe"]["solver"] = cell["preset"]["solver"]
    return cell


def make_tokens(key, batch: int, seq: int, vocab: int) -> dict:
    """The fixed batch, in one jitted call on the device."""
    def make(key):
        tokens = jax.random.randint(key, (batch, seq), 0, vocab, jnp.int32)
        return {"tokens": tokens, "label": jnp.roll(tokens, -1, axis=1)}
    return jax.jit(make)(key)


def reference_hidden(params, tokens, sz: lm_ref.Sizes, q_block: int, **how):
    return lm_ref.hidden(lm_ref.from_net(params, sz), tokens, sz, q_block,
                         **how).reshape(-1, sz.hidden)


def logits_distance(head, x, got, block: int):
    """(sum of squared differences, sum of squares of the reference,
    all finite) between `got` (tokens, V) and the reference's logits of
    its final hidden state `x` under the program's head (V, D), in blocks
    of the vocabulary."""
    vocab = head.shape[0]
    block = min(block, vocab)
    n_blocks = -(-vocab // block)
    pad = n_blocks * block - vocab
    # zero columns past the slice add nothing to either sum
    head = jnp.pad(jnp.asarray(head, jnp.float32), ((0, pad), (0, 0)))
    got = jnp.pad(got, ((0, 0), (0, pad)))

    def one(i):
        want = lm_ref.logits_block(
            {"head": jax.lax.dynamic_slice_in_dim(
                head, i * block, block, axis=0).T}, x, 0, block)
        have = jax.lax.dynamic_slice_in_dim(
            got, i * block, block, axis=1).astype(jnp.float32)
        return (jnp.sum((have - want) ** 2), jnp.sum(want ** 2),
                jnp.all(jnp.isfinite(have)))
    num, den, finite = jax.lax.map(one, jnp.arange(n_blocks))
    return jnp.sum(num), jnp.sum(den), jnp.all(finite)


def logits_verdict(num, den, finite, size: int, limit: float) -> dict:
    rel_rms = float(math.sqrt(num / den))
    return {"rel_rms": rel_rms, "rel_rms_max": limit,
            "reference_rms": float(math.sqrt(den / size)),
            "finite": bool(finite), "ok": bool(finite and rel_rms <= limit)}


def reference_grads(params, feeds: dict, sz: lm_ref.Sizes, spec: dict,
                    **how):
    """`jax.grad` of the reference's blocked loss, in the program's blob
    layouts (`from_net` is linear, so it carries gradients back)."""
    return jax.grad(lambda p: lm_ref.loss_blocked(
        lm_ref.from_net(p, sz), feeds["tokens"], feeds["label"], sz,
        spec["q_block"], spec["vocab_block"], **how))(params)


def leaf_distances(got, want, frozen=()) -> dict:
    """{"layer/blob": (squared norm of the difference, of the reference)}
    over the leaves that train."""
    return {f"{layer}/{blob}": (
        jnp.sum((got[layer][blob].astype(jnp.float32) - g) ** 2),
        jnp.sum(g ** 2))
        for layer, blobs in want.items() for blob, g in blobs.items()
        if (layer, blob) not in frozen}


def grads_verdict(leaves: dict, limits: dict, precision: str) -> dict:
    """The relative norm of the difference, of the worst leaf and of the
    whole tree, each against its limit. A leaf the reference gives no
    gradient at all reads infinite unless the program gives none either."""
    limit = limits["worst_leaf_rel_max"][precision]
    whole_limit = limits["whole_rel_max"][precision]
    rel = {k: (math.sqrt(num / den) if den else (math.inf if num else 0.0))
           for k, (num, den) in leaves.items()}
    worst = max(rel, key=rel.get)
    total = math.sqrt(sum(num for num, _ in leaves.values())
                      / sum(den for _, den in leaves.values()))
    ok = (all(math.isfinite(r) for r in rel.values())
          and rel[worst] <= limit and total <= whole_limit)
    return {"worst_leaf": worst, "worst_leaf_rel": rel[worst],
            "worst_leaf_rel_max": limit, "whole_rel": total,
            "whole_rel_max": whole_limit,
            "leaves": len(rel), "ok": bool(ok),
            "largest": dict(sorted(rel.items(), key=lambda kv: -kv[1])[:4])}


def check_specs(cell: dict) -> tuple[dict, dict]:
    checks, preset = cell["config"]["checks"], cell["preset"]
    logits = {**checks["logits"], **preset}
    return logits, {**logits, **checks["grads"], **preset.get("grads", {})}


def fresh_net(cell: dict, precision: str):
    """The recipe's TRAIN net at the job's batch, as `build_job` sizes it,
    without a Solver: (net, batch, sequence length)."""
    from caffe_mpi_tpu.net import Net
    from caffe_mpi_tpu.proto import NetParameter, SolverParameter

    root = BENCH.parent
    sp = SolverParameter.from_file(
        str(root / cell["config"]["recipe"]["solver"]))
    npar = NetParameter.from_text((root / sp.net).read_text())
    batch = cell["chips"] * cell["preset"].get(
        "batch_per_chip", cell["traffic"]["batch_per_chip"])
    train.set_input_dims(npar, batch)
    net = Net(npar, phase="TRAIN", precision=precision)
    specs = {k: shape for k, (shape, _) in net.feed_specs.items()}
    if set(specs) != {"tokens", "label"}:
        raise ValueError(f"the recipe's feeds are {sorted(specs)}, not "
                         f"tokens and label")
    return net, batch, specs["tokens"][1]


def frozen_leaves(net) -> set:
    return {(layer, blob) for layer, blob, decl
            in net.learnable_param_decls() if decl.lr_mult == 0.0}


def grads_check(cell: dict, net, precision: str, seed: int, feeds: dict,
                sz: lm_ref.Sizes) -> dict:
    """The gradient the first step applies: of the timed net's loss, at the
    timed sizes and precision, on the timed batch and the weights the
    Solver will start from (`Net.init` on the key of `--seed`, as the
    Solver draws them), leaf by leaf against `jax.grad` of the reference's
    blocked loss. Run before the Solver is built: beside its Adam slots
    neither gradient fits on the chip (7.9 GB of state, 8.0 GB of
    temporaries for a gradient that is kept and not consumed)."""
    _, gspec = check_specs(cell)
    params, state = net.init(jax.random.PRNGKey(seed))
    rng = jax.random.PRNGKey(0)
    # kept in the compute type (the cotangent of the cast that made the
    # bf16 copy: nothing is lost)
    kept = jnp.bfloat16 if precision == "bf16" else jnp.float32
    have = jax.jit(lambda p, s, f: jax.tree.map(
        lambda g: g.astype(kept),
        jax.grad(lambda p: net.apply(p, s, f, train=True, rng=rng)[2])(p)))(
            params, state, feeds)
    frozen = frozen_leaves(net)
    leaves = jax.device_get(jax.jit(lambda p, f, have: leaf_distances(
        have, reference_grads(p, f, sz, gspec), frozen))(
            params, feeds, have))
    return {"frozen": sorted("/".join(k) for k in frozen),
            **grads_verdict(leaves, gspec, precision)}


def logits_check(cell: dict, job, feeds: dict, sz: lm_ref.Sizes):
    """Relative RMS distance between the timed net's logits and the plain
    reference's on the timed batch and the solver's fresh weights, the
    reference in blocks of queries and of the vocabulary; and the rows
    each held expert received in that forward pass. Also returns the
    function that counts those rows again on later weights (the compiled
    forward pass, so nothing is built after the window)."""
    from caffe_mpi_tpu.net import Net

    spec, _ = check_specs(cell)
    net = Net(job.npar, phase="TRAIN", precision=job.precision)
    params, state = job.solver.params, job.solver.net_state
    rows_blobs = [f"blk{l}/moe_rows" for l in range(sz.layers)]

    @jax.jit
    def system(params, state, feeds):
        blobs, _, _ = net.apply(params, state, feeds, train=True,
                                rng=jax.random.PRNGKey(0))
        return blobs["logits"], [blobs[b] for b in rows_blobs]

    def count_rows(params) -> list:
        rows = jax.device_get(system(params, state, feeds)[1])
        return [np.asarray(r, np.float64).tolist() for r in rows]

    got, rows = system(params, state, feeds)
    got = got.reshape(-1, sz.vocab)
    x = jax.jit(lambda p, t: reference_hidden(p, t, sz, spec["q_block"]))(
        params, feeds["tokens"])
    num, den, finite = jax.device_get(jax.jit(
        lambda p, x, got: logits_distance(
            p["logits"]["weight"], x, got, spec["vocab_block"]))(
                params, x, got))
    seq = feeds["tokens"].shape[1]
    return count_rows, {
        "blob": "logits", "phase": "TRAIN",
        "sequences": got.shape[0] // seq, "seq_len": seq,
        **logits_verdict(num, den, finite, got.size,
                         spec["rel_rms_max"][job.precision]),
        "moe_rows": [np.asarray(r, np.float64).tolist()
                     for r in jax.device_get(rows)]}


def run(cell: dict, *, seed: int, seconds: float, trace: bool,
        t0: float, out_dir: Path, say) -> dict:
    """Run the cell once; the arguments and the result are `drivers/
    train.py`'s."""
    clock = time.perf_counter
    counter = train.CompileCounter()
    cell = with_preset(cell)
    config, traffic, preset = cell["config"], cell["traffic"], cell["preset"]
    chips = cell["chips"]
    devices = jax.devices()[:chips]
    sz = lm_ref.sizes_from_config(config, preset)
    net, batch, seq = fresh_net(cell, traffic["precision"])
    if not preset and seq != traffic["seq_len"]:
        raise ValueError(f"the recipe's sequence length {seq} is not the "
                         f"mix's seq_len {traffic['seq_len']}")
    key = jax.random.PRNGKey(seed)
    feeds = make_tokens(jax.random.fold_in(key, 1), batch, seq, sz.vocab)
    grads = grads_check(cell, net, traffic["precision"], seed, feeds, sz)
    say(check="grads", **grads)
    t_grads = clock()
    job = train.build_job(cell, seed, out_dir, devices)
    solver, block = job.solver, job.block
    t_built = clock()
    try:
        if job.batch != batch:
            raise ValueError(f"the job's batch {job.batch} is not {batch}")
        count_rows, logits = logits_check(cell, job, feeds, sz)
        say(check="logits", **logits)
        t_checked = clock()
        seen = train.measure(job, lambda it: feeds, seconds=seconds,
                             trace_spec=traffic["trace"] if trace else None,
                             out_dir=out_dir, counter=counter)
        # after the window: the routers are frozen, so routing moves only
        # as far as the other weights' training moves the routers' inputs
        rows_after = count_rows(solver.params)
        say(moe_rows_at_iteration_0=[sum(r) for r in logits["moe_rows"]],
            moe_rows_after_the_window=[sum(r) for r in rows_after],
            iteration=solver.iter)
    finally:
        solver.close()
    peak_bytes, fullest = train.peak_device_bytes(devices)
    say(memory_peak_bytes=peak_bytes, memory_stats_of_fullest_chip=fullest)

    blocks, losses, window_s = seen["blocks"], seen["losses"], seen["window_s"]
    iters = block * len(blocks)
    q1, med, q3 = train.quartiles([1e3 * b["s"] / block for b in blocks])
    plain = [b for b in blocks if not b["traced"]]
    plain_rate = (batch * block * len(plain)
                  / sum(b["s"] for b in plain)) if plain else None
    traced_iters = block * (len(blocks) - len(plain))
    nonfinite = sum(1 for _, loss in losses if not math.isfinite(loss))
    loss_spec = {**config["checks"]["loss"], **preset.get("loss", {})}
    ceiling = loss_spec["share_of_ln_vocab_max"] * math.log(sz.vocab)
    reached = [(it, loss) for it, loss in losses
               if it >= loss_spec["by_iteration"]]
    loss_ok = bool(reached) and reached[0][1] <= ceiling
    say(check="loss", first=losses[0][1], ln_vocab=math.log(sz.vocab),
        reached=reached[:1], last=losses[-1], ceiling=ceiling,
        share_of_ln_vocab_max=loss_spec["share_of_ln_vocab_max"],
        by_iteration=loss_spec["by_iteration"], nonfinite=nonfinite,
        ok=loss_ok, trajectory=losses[:12])
    counts = {k: seen[k] for k in (
        "dispatches", "host_syncs", "guard_syncs", "skipped_steps",
        "overflow_steps", "loss_scale", "compiles_in_window", "profiler_s")}
    setup_s = seen["t_begin"] - t0
    parts = {"setup_grads_s": t_grads - t0,
             "setup_build_s": t_built - t_grads,
             "setup_check_s": t_checked - t_built,
             "setup_compile_s": seen["setup_compile_s"]}
    say(setup_s=setup_s, **parts,
        setup_warm_block_s=setup_s - sum(parts.values()))
    rate = batch * iters / window_s
    say(window_s=window_s, blocks=len(blocks), block_iters=block,
        iters=iters, samples_per_iter=batch, tokens_per_sample=seq,
        tokens_per_s=rate * seq, step_ms_median=med,
        step_ms_q1=q1, step_ms_q3=q3, block_s=[b["s"] for b in blocks],
        block_end_syncs=len(blocks),
        programs_built=counter.built, cache_hits=counter.hits,
        compiled=counter.compiled, **counts)

    summary = None
    pallas_ok = True
    if seen["xplane"] is not None:
        summary = trace_reduce.reduce_xplane(str(seen["xplane"]))
        (out_dir / "trace_summary.json").write_text(
            json.dumps(summary, indent=1))
    if summary is not None:
        expected = config["checks"]["pallas_calls_per_step"][job.precision]
        calls = sum(k["count"] for k in summary["custom_calls"].values())
        pallas_ok = calls == expected * traced_iters
        say(check="pallas", calls_in_slice=calls, traced_iters=traced_iters,
            expected_per_step=expected, ok=pallas_ok,
            kernels={k: v["count"]
                     for k, v in summary["custom_calls"].items()})

    record = {
        "cell": cell["name"], "chips": chips, "precision": job.precision,
        "samples_per_iter": batch, "block_iters": block, "iters": iters,
        "window_s": window_s, "seq_len": seq,
        "untraced_samples_per_s": plain_rate, "traced_iters": traced_iters,
        "programs_built": counter.built, "cache_hits": counter.hits,
        "setup_s": setup_s, **parts, "memory_peak_bytes": peak_bytes,
        "macs_per_sample": lm_ref.macs_per_sample(sz, seq),
        "sizes": lm_ref.sizes_record(sz),
        "moe_rows": logits["moe_rows"], "moe_rows_after": rows_after,
        "step_ms": {"q1": q1, "median": med, "q3": q3},
        "logits": logits, "grads": grads, "losses": losses, **counts,
    }
    return {
        "correct": bool(logits["ok"] and grads["ok"] and loss_ok
                        and nonfinite == 0
                        and seen["compiles_in_window"] == 0 and pallas_ok),
        "attempted": iters,
        "failed": seen["skipped_steps"] + nonfinite,
        "end_to_end": {"train_samples_per_s": rate, "setup_s": setup_s},
        "record": record,
        "trace": summary,
    }


# -- controls: the reference with one fault planted, in the program's place --

def _renormalising(sz: lm_ref.Sizes):
    """`lm_ref.route` with the weights renormalised over the held experts:
    the wrong reading of equation 8."""
    sound = lm_ref.route

    def route(r, top_k):
        idx, w = sound(r, top_k)
        mine = (idx >= sz.first_expert) & (idx < sz.first_expert
                                           + sz.experts_held)
        w = jnp.where(mine, w, 0.0)
        return idx, w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-30)
    return route


def faults(sz: lm_ref.Sizes, seq: int) -> dict:
    """name -> (sound?, sizes, keyword arguments of the reference,
    replacement for `lm_ref.route`)."""
    none = (0,) * sz.layers
    cut = dataclasses.replace
    return {
        "operands_bf16": (True, sz, {"operand_dtype": jnp.bfloat16}, None),
        "operands_f8_e5m2": (False, sz,
                             {"operand_dtype": jnp.float8_e5m2}, None),
        "operands_f8_e4m3": (False, sz,
                             {"operand_dtype": jnp.float8_e4m3fn}, None),
        "no_window": (False, cut(sz, window_layout=none), {}, None),
        "no_rotary": (False, cut(sz, rope_layout=none), {}, None),
        "w_renormalised_over_held": (False, sz, {}, _renormalising(sz)),
        "half_the_positions": (False, sz, {"positions": seq // 2}, None),
    }


def controls(cell: dict, seed: int, say) -> bool:
    """The set-up's two comparisons, against the same limits, with
    the reference under each planted fault where the program stands. True
    if every fault came out not correct and the sound control correct."""
    cell = with_preset(cell)
    config, traffic, preset = cell["config"], cell["traffic"], cell["preset"]
    sz = lm_ref.sizes_from_config(config, preset)
    net, batch, seq = fresh_net(cell, "f32")
    key = jax.random.PRNGKey(seed)
    params, _ = net.init(key)
    feeds = make_tokens(jax.random.fold_in(key, 1), batch, seq, sz.vocab)
    spec, gspec = check_specs(cell)
    precision = traffic["precision"]
    as_expected = True

    def planted(fn, route):
        old = lm_ref.route
        lm_ref.route = route or old
        try:
            return fn()
        finally:
            lm_ref.route = old

    chosen = faults(sz, seq)

    hidden = lambda sizes, **how: jax.jit(
        lambda p, t: reference_hidden(p, t, sizes, spec["q_block"], **how))(
            params, feeds["tokens"])
    logits = lambda x, dt: jax.jit(lambda p, x: lm_ref.logits_block(
        {"head": jnp.asarray(p["logits"]["weight"], jnp.float32).T}, x, 0,
        sz.vocab, dt))(params, x)
    distance = jax.jit(lambda p, x, got: logits_distance(
        p["logits"]["weight"], x, got, spec["vocab_block"]))
    x = hidden(sz)
    for name, (sound, sizes, how, route) in chosen.items():
        if "positions" in how:   # a fault of the loss, not of the logits
            continue
        got = planted(lambda: logits(hidden(sizes, **how),
                                     how.get("operand_dtype")), route)
        verdict = logits_verdict(
            *jax.device_get(distance(params, x, got)), got.size,
            spec["rel_rms_max"][precision])
        as_expected &= verdict["ok"] == sound
        say(control="logits", fault=name, sound=sound, correct=verdict["ok"],
            **verdict)
    del x, got

    grad = lambda sizes, **how: jax.jit(
        lambda p, f: reference_grads(p, f, sizes, gspec, **how))(
            params, feeds)
    frozen = frozen_leaves(net)
    distance = jax.jit(lambda have, want: leaf_distances(have, want, frozen))
    want = grad(sz)
    for name, (sound, sizes, how, route) in chosen.items():
        have = planted(lambda: grad(sizes, **how), route)
        verdict = grads_verdict(jax.device_get(distance(have, want)), gspec,
                                precision)
        del have   # 2.6 GB at the timed size, beside the next one's 7
        as_expected &= verdict["ok"] == sound
        say(control="grads", fault=name, sound=sound, correct=verdict["ok"],
            **verdict)
    say(controls_as_expected=bool(as_expected))
    return bool(as_expected)


def main(argv: list[str]) -> int:
    import argparse
    import os
    ap = argparse.ArgumentParser(description=controls.__doc__)
    ap.add_argument("--controls", action="store_true", required=True)
    ap.add_argument("--workload", default="smallthinker_bf16_s8k_ep4share")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    harness = _load(BENCH / "run.py")
    cell = harness.load_cell(args.workload, args.rehearse)
    say = lambda **fields: print(json.dumps(fields), flush=True)
    return 0 if controls(cell, args.seed, say) else 1


if __name__ == "__main__":
    sys.path.insert(0, str(BENCH.parent))
    sys.exit(main(sys.argv[1:]))
