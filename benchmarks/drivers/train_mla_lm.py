"""The `train_mla_lm` driver: one training job of the latent-attention
language model (`configs/joyai_llm_flash.json`), dispatched as `caffe train
-solver <recipe> -synthetic -precision bf16` dispatches it.

It is `drivers/train_lm.py`'s flow with `reference/joyai_ref.py` as the
yardstick; what it can it loads from that file and from `drivers/train.py`
(`build_job`, `measure`, the compile counter, the two distances and their
verdicts), nothing there is edited. What differs:

- three feeds: token ids uniform over the vocabulary slice from `--seed`,
  `label` the next token (which the MTP module also embeds) and `label_mtp`
  the one after, both wrapping, int32 on the device;
- two heads: the logits check compares the main logits and the MTP
  module's, stacked, in one relative RMS; the gradient check is of the
  whole loss, L_main + 0.3 L_mtp;
- the rows each held expert received are read from the four expert layers
  and the MTP module's (`routed_rows`), at set-up and after the window;
- the loss ceiling is a share of the fixed batch's loss at iteration 0 (a
  sum of two cross-entropies has no ln(vocabulary) of its own).

`correct` = (a) both comparisons within the configuration's limits, the
gradient check before the `Solver` is built, the logits check on the
solver's fresh weights; (b) every loss finite, and the ceiling met by the
stated iteration; (c) no program built inside the window; (d) as many
Mosaic calls a step as the configuration states.

`python3 benchmarks/drivers/train_mla_lm.py --controls [--seed n]
[--rehearse]` puts the reference with one fault planted (`faults`) in the
program's place and runs the same two comparisons against the same limits:
each must come out not correct, and the reference with operands rounded to
bf16, the program's own precision, correct.
"""

from __future__ import annotations

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
from reference import joyai_ref  # noqa: E402


def _load(path: Path):
    name = f"bench_{path.parent.name}_{path.stem}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


lm = _load(BENCH / "drivers" / "train_lm.py")
train = lm.train
FEEDS = {"tokens", "label", "label_mtp"}


def make_tokens(key, batch: int, seq: int, vocab: int) -> dict:
    """The fixed batch, in one jitted call on the device."""
    def make(key):
        tokens = jax.random.randint(key, (batch, seq), 0, vocab, jnp.int32)
        return {"tokens": tokens, "label": jnp.roll(tokens, -1, axis=1),
                "label_mtp": jnp.roll(tokens, -2, axis=1)}
    return jax.jit(make)(key)


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
    if set(specs) != FEEDS:
        raise ValueError(f"the recipe's feeds are {sorted(specs)}, not "
                         f"{sorted(FEEDS)}")
    return net, batch, specs["tokens"][1]


def rows_blobs(sz: joyai_ref.Sizes) -> list[str]:
    return [f"blk{l}/moe_rows" for l in range(sz.dense_layers, sz.layers)] \
        + ["mtp/moe_rows"]


def reference_hidden(params, feeds: dict, sz, q_block: int, **how):
    """What the head reads for both predictions, stacked: (2 tokens, D)."""
    main, mtp = joyai_ref.hidden(joyai_ref.from_net(params, sz),
                                 feeds["tokens"], feeds["label"], sz,
                                 q_block, **how)
    return jnp.concatenate([main.reshape(-1, sz.hidden),
                            mtp.reshape(-1, sz.hidden)])


def reference_grads(params, feeds: dict, sz, spec: dict, **how):
    """`jax.grad` of the reference's blocked loss, in the program's blob
    layouts (`from_net` is linear, so it carries gradients back)."""
    return jax.grad(lambda p: joyai_ref.loss_blocked(
        joyai_ref.from_net(p, sz), feeds["tokens"], feeds["label"],
        feeds["label_mtp"], sz, spec["q_block"], spec["vocab_block"],
        **how))(params)


def grads_check(cell: dict, net, precision: str, seed: int, feeds: dict,
                sz) -> dict:
    """The gradient the first step applies (`train_lm.grads_check`, with
    this configuration's reference), before the Solver is built: beside its
    8.2 GB of state neither gradient fits on the chip."""
    _, gspec = lm.check_specs(cell)
    params, state = net.init(jax.random.PRNGKey(seed))
    rng = jax.random.PRNGKey(0)
    kept = jnp.bfloat16 if precision == "bf16" else jnp.float32
    have = jax.jit(lambda p, s, f: jax.tree.map(
        lambda g: g.astype(kept),
        jax.grad(lambda p: net.apply(p, s, f, train=True, rng=rng)[2])(p)))(
            params, state, feeds)
    frozen = lm.frozen_leaves(net)
    leaves = jax.device_get(jax.jit(lambda p, f, have: lm.leaf_distances(
        have, reference_grads(p, f, sz, gspec), frozen))(
            params, feeds, have))
    return {"frozen": sorted("/".join(k) for k in frozen),
            **lm.grads_verdict(leaves, gspec, precision)}


def logits_check(cell: dict, job, feeds: dict, sz):
    """Relative RMS distance between the timed net's two sets of logits and
    the plain reference's on the timed batch and the solver's fresh
    weights, and the rows each held expert received in that forward pass;
    also the function that counts those rows again on later weights (the
    compiled forward pass, so nothing is built after the window)."""
    from caffe_mpi_tpu.net import Net

    spec, _ = lm.check_specs(cell)
    net = Net(job.npar, phase="TRAIN", precision=job.precision)
    params, state = job.solver.params, job.solver.net_state
    counted = rows_blobs(sz)

    @jax.jit
    def system(params, state, feeds):
        blobs, _, _ = net.apply(params, state, feeds, train=True,
                                rng=jax.random.PRNGKey(0))
        both = jnp.concatenate([blobs[b].reshape(-1, sz.vocab)
                                for b in ("logits", "mtp/logits")])
        return both, [blobs[b] for b in counted]

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
        "blobs": ["logits", "mtp/logits"], "phase": "TRAIN",
        "sequences": got.shape[0] // (2 * seq), "seq_len": seq,
        **lm.logits_verdict(num, den, finite, got.size,
                            spec["rel_rms_max"][job.precision]),
        "routed_rows": as_lists(rows)}


def run(cell: dict, *, seed: int, seconds: float, trace: bool,
        t0: float, out_dir: Path, say) -> dict:
    """Run the cell once; the arguments and the result are `drivers/
    train.py`'s."""
    clock = time.perf_counter
    counter = train.CompileCounter()
    cell = lm.with_preset(cell)
    config, traffic, preset = cell["config"], cell["traffic"], cell["preset"]
    chips = cell["chips"]
    devices = jax.devices()[:chips]
    sz = joyai_ref.sizes_from_config(config, preset)
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
        say(routed_rows_at_iteration_0=[sum(r) for r in
                                        logits["routed_rows"]],
            routed_rows_after_the_window=[sum(r) for r in rows_after],
            layers=rows_blobs(sz), iteration=solver.iter)
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
    first = losses[0][1]
    ceiling = loss_spec["share_of_initial_max"] * first
    reached = [(it, loss) for it, loss in losses
               if it >= loss_spec["by_iteration"]]
    loss_ok = bool(reached) and reached[0][1] <= ceiling
    say(check="loss", first=first, ln_vocab=math.log(sz.vocab),
        mtp_weight=sz.mtp_weight, reached=reached[:1], last=losses[-1],
        ceiling=ceiling,
        share_of_initial_max=loss_spec["share_of_initial_max"],
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
        "macs_per_sample": joyai_ref.macs_per_sample(sz, seq),
        "mla_sizes": joyai_ref.sizes_record(sz),
        "routed_rows": logits["routed_rows"], "routed_rows_after": rows_after,
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

def _bias_weighs(scores, bias, sz):
    """`joyai_ref.route` with the selection bias in the weights too: the
    wrong reading of equation 6."""
    _, idx = jax.lax.top_k(scores + bias, sz.top_k)
    chosen = jnp.take_along_axis(scores + bias, idx, axis=-1)
    return idx, sz.scaling * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def _renormalising(sound):
    """`joyai_ref.route` with the weights renormalised over the held
    experts: the wrong reading of equation 7."""
    def route(scores, bias, sz):
        idx, w = sound(scores, bias, sz)
        mine = (idx >= sz.first_expert) & (idx < sz.first_expert
                                           + sz.experts_held)
        w = jnp.where(mine, w, 0.0)
        return idx, sz.scaling * w / jnp.maximum(
            jnp.sum(w, -1, keepdims=True), 1e-30)
    return route


def faults(sz: joyai_ref.Sizes, seq: int) -> dict:
    """name -> (sound?, sizes, keyword arguments of the reference,
    replacement for `joyai_ref.route`, a fault of the loss alone?)."""
    cut = dataclasses.replace
    return {
        "operands_bf16": (True, sz, {"operand_dtype": jnp.bfloat16}, None,
                          False),
        "operands_f8_e5m2": (False, sz, {"operand_dtype": jnp.float8_e5m2},
                             None, False),
        "operands_f8_e4m3": (False, sz,
                             {"operand_dtype": jnp.float8_e4m3fn}, None,
                             False),
        "no_rotary": (False, sz, {"rotary": "none"}, None, False),
        "rotary_over_the_whole_head": (False, sz, {"rotary": "whole"}, None,
                                       False),
        "bias_weighs_as_well": (False, sz, {}, _bias_weighs, False),
        "no_scaling_factor": (False, cut(sz, scaling=1.0), {}, None, False),
        "no_shared_expert": (False, cut(sz, shared_experts=0), {}, None,
                             False),
        "w_renormalised_over_held": (False, sz, {},
                                     _renormalising(joyai_ref.route), False),
        "no_mtp_loss": (False, cut(sz, mtp_weight=0.0), {}, None, True),
        "half_the_positions": (False, sz, {"positions": seq // 2}, None,
                               True),
    }


def controls(cell: dict, seed: int, say) -> bool:
    """The set-up's two comparisons, against the same limits, with the
    reference under each planted fault where the program stands. True if
    every fault came out not correct and the sound control correct."""
    cell = lm.with_preset(cell)
    config, traffic, preset = cell["config"], cell["traffic"], cell["preset"]
    sz = joyai_ref.sizes_from_config(config, preset)
    net, batch, seq = fresh_net(cell, "f32")
    key = jax.random.PRNGKey(seed)
    params, _ = net.init(key)
    feeds = make_tokens(jax.random.fold_in(key, 1), batch, seq, sz.vocab)
    spec, gspec = lm.check_specs(cell)
    precision = traffic["precision"]
    as_expected = True

    def planted(fn, route):
        old = joyai_ref.route
        joyai_ref.route = route or old
        try:
            return fn()
        finally:
            joyai_ref.route = old

    chosen = faults(sz, seq)
    hidden = lambda sizes, **how: jax.jit(
        lambda p, f: reference_hidden(p, f, sizes, spec["q_block"], **how))(
            params, feeds)
    logits = lambda x, dt: jax.jit(lambda p, x: joyai_ref.logits_block(
        {"head": jnp.asarray(p["logits"]["weight"], jnp.float32).T}, x, 0,
        sz.vocab, dt))(params, x)
    distance = jax.jit(lambda p, x, got: lm.logits_distance(
        p["logits"]["weight"], x, got, spec["vocab_block"]))
    x = hidden(sz)
    for name, (sound, sizes, how, route, loss_only) in chosen.items():
        if loss_only:
            continue
        got = planted(lambda: logits(hidden(sizes, **how),
                                     how.get("operand_dtype")), route)
        verdict = lm.logits_verdict(
            *jax.device_get(distance(params, x, got)), got.size,
            spec["rel_rms_max"][precision])
        as_expected &= verdict["ok"] == sound
        say(control="logits", fault=name, sound=sound, correct=verdict["ok"],
            **verdict)
    del x, got

    grad = lambda sizes, **how: jax.jit(
        lambda p, f: reference_grads(p, f, sizes, gspec, **how))(
            params, feeds)
    frozen = lm.frozen_leaves(net)
    distance = jax.jit(lambda have, want: lm.leaf_distances(have, want,
                                                            frozen))
    want = grad(sz)
    for name, (sound, sizes, how, route, _) in chosen.items():
        have = planted(lambda: grad(sizes, **how), route)
        verdict = lm.grads_verdict(jax.device_get(distance(have, want)),
                                   gspec, precision)
        del have   # 2.7 GB at the timed size, beside the next one's
        as_expected &= verdict["ok"] == sound
        say(control="grads", fault=name, sound=sound, correct=verdict["ok"],
            **verdict)
    say(controls_as_expected=bool(as_expected))
    return bool(as_expected)


def main(argv: list[str]) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=controls.__doc__)
    ap.add_argument("--controls", action="store_true", required=True)
    ap.add_argument("--workload", default="joyai_flash_bf16_s8k_epshare")
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
