"""The `train_ssm_lm` driver: one language-model training job of a model
whose layers alternate by a pattern between state-space mixers, expert
layers and attention (`configs/nemotron3_nano_30b_a3b.json`), dispatched as
`caffe train -solver <recipe> -synthetic -precision bf16` dispatches it.

It is `drivers/train_lm.py`'s flow with `reference/nemotron_ref.py` as the
yardstick; what it can it loads from that file, from `drivers/
train_cca_lm.py` and from `drivers/train.py` (`build_job`, `measure`, the
compile counter, the fixed batch, the two distances and their verdicts, the
leaf rule), nothing there is edited. What differs:

- the reference's state-space recurrence is a scan over time, in blocks of
  `checks.logits.time_block` positions (a block's states are computed again
  under `jax.grad`);
- a leaf of the gradient check is held to the scale of its kind
  (`train_cca_lm.held_to_its_kind`): its difference is taken against the
  larger of its own reference norm and half the root mean square of the
  same blob's reference norms over the layers, so a leaf whose own gradient
  comes out near nought (one of the 64-element vectors a head) is not
  divided by it; and the worst leaf that is no bank of matrices has a
  limit of its own (`grads_verdict`); no leaf is named;
- a third comparison, `scan_probe`: `ops/ssd.py`'s scan alone at the timed
  shapes against equation 5 one position a step, on inputs where the
  decays decide the result (`nemotron_ref.probe_inputs`), each head held to
  its own norm: in the whole net on fresh weights what came through the
  state is a few per cent of a layer's output, and decay arithmetic in bf16
  reads under the program's own rounding there;
- only the pattern's `E` layers have rows to count: they are under
  `nemotron_rows`, read from the set-up's forward pass and once more after
  the window, never inside it.

`correct` = (a) the logits of the timed `Net` at the timed sizes and
precision, on the solver's fresh weights and the timed batch, within a
relative RMS of the reference computed in blocks, and the gradient of the
timed `Net`'s loss on the same weights and batch (what the first step
applies), leaf by leaf and as a whole, within a relative norm of `jax.grad`
of the reference's blocked loss, and the scan's probe within its limit; (b)
every loss finite, and the ceiling met by the stated iteration; (c) no
program built inside the window; (d) as many Mosaic calls a step as the
configuration states.

`python3 benchmarks/drivers/train_ssm_lm.py --controls [--seed n]
[--rehearse] [--only fault ...]` puts the reference with one fault planted
(`faults`) in the program's place and runs the same comparisons against
the same limits: each must come out not correct by at least one limit, and
the reference with operands rounded to bf16, the program's own precision,
correct by all.
"""

from __future__ import annotations

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
from reference import nemotron_ref  # noqa: E402


def _load(path: Path):
    name = f"bench_{path.parent.name}_{path.stem}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


cca = _load(BENCH / "drivers" / "train_cca_lm.py")
lm = cca.lm
train = lm.train


def grads_verdict(leaves: dict, limits: dict, precision: str,
                  banks=()) -> dict:
    """`train_cca_lm.grads_verdict` (the worst leaf held to its kind, the
    whole tree as it is) with a third limit: the worst leaf that is no
    bank of matrices. A bank (`banks`: the leaves of rank 3, one matrix an
    expert) sums the few hundred rows routed to each expert, and the few
    tokens that bf16 scores send to another expert than float32 ones are
    much of its difference (0.18-0.20 here); every other leaf sums 8,192
    rows and reads several times lower, so a fault that a bank's noise
    would hide (rotary positions applied: 0.28 on the attention layer's
    products) is held to what those leaves read. No leaf is named."""
    verdict = cca.grads_verdict(leaves, limits, precision)
    held = cca.held_to_its_kind(leaves)
    rel = {k: (math.sqrt(num / den) if den else (math.inf if num else 0.0))
           for k, (num, den) in held.items() if k not in banks}
    worst = max(rel, key=rel.get)
    limit = limits["worst_other_leaf_rel_max"][precision]
    return {**verdict, "worst_other_leaf": worst,
            "worst_other_leaf_rel": rel[worst],
            "worst_other_leaf_rel_max": limit,
            "largest_others": dict(sorted(rel.items(),
                                          key=lambda kv: -kv[1])[:6]),
            "ok": bool(verdict["ok"] and rel[worst] <= limit)}


def bank_leaves(params) -> set:
    """The leaves of rank 3: one matrix an expert."""
    return {f"{layer}/{blob}" for layer, blobs in params.items()
            for blob, a in blobs.items() if jnp.ndim(a) >= 3}


def head_of(params):
    return params["logits"]["weight"]


def reference_hidden(params, tokens, sz, spec: dict, **how):
    return nemotron_ref.hidden(
        nemotron_ref.from_net(params, sz), tokens, sz, spec["q_block"],
        time_block=spec["time_block"], **how).reshape(-1, sz.hidden)


def reference_grads(params, feeds: dict, sz, spec: dict, **how):
    """`jax.grad` of the reference's blocked loss, in the program's blob
    layouts (`from_net` is linear, so it carries gradients back)."""
    return jax.grad(lambda p: nemotron_ref.loss_blocked(
        nemotron_ref.from_net(p, sz), feeds["tokens"], feeds["label"], sz,
        spec["q_block"], spec["vocab_block"],
        time_block=spec["time_block"], **how))(params)


def grads_check(cell: dict, net, precision: str, seed: int, feeds: dict,
                sz) -> dict:
    """The gradient the first step applies (`train_lm.grads_check`, with
    this configuration's reference), before the Solver is built: beside its
    state neither gradient fits on the chip."""
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
    return {"frozen": len(frozen),
            **grads_verdict(leaves, gspec, precision, bank_leaves(params))}


def scan_probe(cell: dict, sz, seq: int, key, precision: str,
               how: dict | None = None) -> dict:
    """The program's scan alone (`ops/ssd.py`, forward), at the timed shapes
    and precision, against equation 5 one position a step in float32, on
    `nemotron_ref.probe_inputs`: the worst head's relative RMS distance,
    each head against its own norm, and the whole output's, against
    `checks.scan_probe`. With `how`, the reference under that fault stands
    in the program's place (`--controls`)."""
    from caffe_mpi_tpu.ops.ssd import ssd

    config = cell["config"]
    spec = {**config["checks"]["scan_probe"],
            **cell["preset"].get("scan_probe", {})}
    dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32
    time_step = [config[f"time_step_{end}"] for end in ("min", "max", "floor")]
    args = jax.jit(lambda key: nemotron_ref.probe_inputs(
        key, seq, sz, time_step, dtype))(key)
    f32 = lambda t: t.astype(jnp.float32)
    reference = lambda **how: jax.jit(
        lambda x, raw, dt_bias, a_log, b, c: nemotron_ref.scan(
            f32(x), f32(raw), dt_bias, a_log, f32(b), f32(c), sz.chunk,
            min(spec["time_block"], seq), **how))(*args)
    got = reference(**how) if how is not None else jax.jit(
        lambda x, raw, dt_bias, a_log, b, c: ssd(
            x, raw, a_log, b, c, jnp.zeros_like(a_log), dt_bias, sz.chunk))(
                *args)
    num, den = jax.device_get(jax.jit(lambda got, want: (
        jnp.sum((f32(got) - want) ** 2, axis=(0, 1, 3)),
        jnp.sum(want ** 2, axis=(0, 1, 3))))(got, reference()))
    heads = np.sqrt(num / den)
    worst, limit = int(np.argmax(heads)), spec["worst_head_rel_max"][precision]
    finite = bool(np.isfinite(heads).all())
    return {"seq_len": seq, "worst_head": worst,
            "worst_head_rel": float(heads[worst]),
            "worst_head_rel_max": limit,
            "median_head_rel": float(np.median(heads)),
            "whole_rel": float(np.sqrt(num.sum() / den.sum())),
            "finite": finite,
            "ok": bool(finite and heads[worst] <= limit)}


def logits_check(cell: dict, job, feeds: dict, sz):
    """`train_lm.logits_check` with this configuration's reference and the
    rows each held expert of the pattern's `E` layers received; also
    returns the function that counts those rows again on later weights (the
    compiled forward pass)."""
    from caffe_mpi_tpu.net import Net

    spec, _ = lm.check_specs(cell)
    net = Net(job.npar, phase="TRAIN", precision=job.precision)
    params, state = job.solver.params, job.solver.net_state
    rows_blobs = [f"blk{l}/moe_rows" for l in sz.of_kind("E")]

    @jax.jit
    def system(params, state, feeds):
        blobs, _, _ = net.apply(params, state, feeds, train=True,
                                rng=jax.random.PRNGKey(0))
        return blobs["logits"], [blobs[b] for b in rows_blobs]

    as_lists = lambda rows: [np.asarray(r, np.float64).tolist()
                             for r in jax.device_get(rows)]
    count_rows = lambda params: as_lists(system(params, state, feeds)[1])

    got, rows = system(params, state, feeds)
    got = got.reshape(-1, sz.vocab)
    x = jax.jit(lambda p, t: reference_hidden(p, t, sz, spec))(
        params, feeds["tokens"])
    num, den, finite = jax.device_get(jax.jit(
        lambda p, x, got: lm.logits_distance(
            head_of(p), x, got, spec["vocab_block"]))(params, x, got))
    seq = feeds["tokens"].shape[1]
    return count_rows, {
        "blob": "logits", "phase": "TRAIN",
        "sequences": got.shape[0] // seq, "seq_len": seq,
        **lm.logits_verdict(num, den, finite, got.size,
                            spec["rel_rms_max"][job.precision]),
        "nemotron_rows": as_lists(rows)}


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
    sz = nemotron_ref.sizes_from_config(config, preset)
    net, batch, seq = lm.fresh_net(cell, traffic["precision"])
    if not preset and seq != traffic["seq_len"]:
        raise ValueError(f"the recipe's sequence length {seq} is not the "
                         f"mix's seq_len {traffic['seq_len']}")
    key = jax.random.PRNGKey(seed)
    feeds = lm.make_tokens(jax.random.fold_in(key, 1), batch, seq, sz.vocab)
    grads = grads_check(cell, net, traffic["precision"], seed, feeds, sz)
    say(check="grads", **grads)
    t_grads = clock()
    probe = scan_probe(cell, sz, seq, jax.random.fold_in(key, 2),
                       traffic["precision"])
    say(check="scan_probe", **probe)
    t_probe = clock()
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
        say(nemotron_rows_at_iteration_0=[sum(r) for r
                                          in logits["nemotron_rows"]],
            nemotron_rows_after_the_window=[sum(r) for r in rows_after],
            rows_a_held_expert_at_even_routing=batch * seq * sz.top_k
            / sz.experts, iteration=solver.iter)
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
             "setup_probe_s": t_probe - t_grads,
             "setup_build_s": t_built - t_probe,
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
        "macs_per_sample": nemotron_ref.macs_per_sample(sz, seq),
        "nemotron_sizes": nemotron_ref.sizes_record(sz),
        "nemotron_rows": logits["nemotron_rows"],
        "nemotron_rows_after": rows_after,
        "step_ms": {"q1": q1, "median": med, "q3": q3},
        "logits": logits, "grads": grads, "scan_probe": probe,
        "losses": losses, **counts,
    }
    return {
        "correct": bool(logits["ok"] and grads["ok"] and probe["ok"]
                        and loss_ok
                        and nonfinite == 0
                        and seen["compiles_in_window"] == 0 and pallas_ok),
        "attempted": iters,
        "failed": seen["skipped_steps"] + nonfinite,
        "end_to_end": {"train_samples_per_s": rate, "setup_s": setup_s},
        "record": record,
        "trace": summary,
    }


# -- controls: the reference with one fault planted, in the program's place --

def faults() -> dict:
    """name -> (sound?, keyword arguments of the reference)."""
    flip = lambda term: {term: not nemotron_ref.FAULTS[term]}
    return {
        "operands_bf16": (True, {"operand_dtype": jnp.bfloat16}),
        "operands_f8_e4m3": (False, {"operand_dtype": jnp.float8_e4m3fn}),
        "decay_arithmetic_in_bf16": (False, {"decay_dtype": jnp.bfloat16}),
        "state_dropped_at_chunk_edges": (False, flip("carry_state")),
        "no_softplus": (False, flip("softplus")),
        "d_left_out": (False, flip("d_term")),
        "gate_after_the_norm": (False, flip("gate_first")),
        "one_norm_over_every_channel": (False, flip("norm_in_groups")),
        "group_by_remainder": (False, flip("group_by_division")),
        "a_tap_on_the_next_row": (False, flip("causal_taps")),
        "relu_for_relu_squared": (False, flip("squared")),
        "a_gated_expert": (False, flip("gated_expert")),
        "no_scaling_factor": (False, flip("scaling")),
        "weights_not_renormalised": (False, flip("renormalised")),
        "rotary_applied": (False, flip("rotary")),
    }


def controls(cell: dict, seed: int, say, only=()) -> bool:
    """The set-up's three comparisons, against the same limits, with the
    reference under each planted fault where the program stands. True if
    every fault came out not correct BY AT LEAST ONE LIMIT and the sound
    control correct by all (the scan's probe judges only the faults whose
    term its reference reads, `nemotron_ref.SCAN_TERMS`)."""
    cell = lm.with_preset(cell)
    config, traffic, preset = cell["config"], cell["traffic"], cell["preset"]
    sz = nemotron_ref.sizes_from_config(config, preset)
    net, batch, seq = lm.fresh_net(cell, "f32")
    key = jax.random.PRNGKey(seed)
    params, _ = net.init(key)
    feeds = lm.make_tokens(jax.random.fold_in(key, 1), batch, seq, sz.vocab)
    spec, gspec = lm.check_specs(cell)
    precision = traffic["precision"]
    chosen = {name: fault for name, fault in faults().items()
              if not only or name in only}
    passed = {name: [] for name in chosen}

    hidden = lambda **how: jax.jit(
        lambda p, t: reference_hidden(p, t, sz, spec, **how))(
            params, feeds["tokens"])
    logits = lambda x, dt: jax.jit(
        lambda p, x: nemotron_ref.logits_block(
            {"head": jnp.asarray(head_of(p), jnp.float32).T}, x, 0, sz.vocab,
            dt))(params, x)
    distance = jax.jit(lambda p, x, got: lm.logits_distance(
        head_of(p), x, got, spec["vocab_block"]))
    x = hidden()
    for name, (sound, how) in chosen.items():
        got = None   # one (tokens, vocabulary) array at a time
        got = logits(hidden(**how), how.get("operand_dtype"))
        verdict = lm.logits_verdict(
            *jax.device_get(distance(params, x, got)), got.size,
            spec["rel_rms_max"][precision])
        passed[name].append(verdict["ok"])
        say(control="logits", fault=name, sound=sound,
            correct=verdict["ok"], **verdict)
    del x, got

    for name, (sound, how) in chosen.items():
        if not set(how) & set(nemotron_ref.SCAN_TERMS):
            continue   # the probe's reference does not read the term
        verdict = scan_probe(cell, sz, seq, jax.random.fold_in(key, 2),
                             precision, how)
        passed[name].append(verdict["ok"])
        say(control="scan_probe", fault=name, sound=sound,
            correct=verdict["ok"], **verdict)

    grad = lambda **how: jax.jit(
        lambda p, f: reference_grads(p, f, sz, gspec, **how))(params, feeds)
    frozen = lm.frozen_leaves(net)
    distance = jax.jit(lambda have, want: lm.leaf_distances(have, want,
                                                            frozen))
    want = grad()
    for name, (sound, how) in chosen.items():
        have = grad(**how)
        verdict = grads_verdict(jax.device_get(distance(have, want)), gspec,
                                precision, bank_leaves(params))
        del have   # 2.7 GB at the timed size, beside the next one's
        passed[name].append(verdict["ok"])
        say(control="grads", fault=name, sound=sound,
            correct=verdict["ok"], **verdict)
    # a fault is caught if at least one limit refuses it; the sound control
    # has to pass every one
    as_expected = all(all(passed[name]) == sound
                      for name, (sound, _) in chosen.items())
    say(controls_as_expected=bool(as_expected),
        passed_a_limit=sorted(name for name, oks in passed.items()
                              if any(oks) and not chosen[name][0]))
    return bool(as_expected)


def main(argv: list[str]) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=controls.__doc__)
    ap.add_argument("--controls", action="store_true", required=True)
    ap.add_argument("--workload", default="nemotron3_nano_bf16_s8k_ep16share")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--only", nargs="*", default=(),
                    help="these faults alone")
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
