"""The `train_bd_lm` driver: one block-diffusion training job of a language
model (`configs/sdar_30b_a3b.json`), dispatched as `caffe train -solver
<recipe> -synthetic -precision bf16` dispatches it.

It is `drivers/train_lm.py`'s flow with `reference/sdar_ref.py` as the
yardstick; what it can it loads from that file and from `drivers/train.py`
(`build_job`, `measure`, the compile counter, the two distances and their
verdicts), nothing there is edited. What differs:

- one feed: the clean token ids, uniform over the vocabulary slice WITHOUT
  the mask id (the slice's last id) from `--seed`, int32 on the device, one
  fixed batch. The net draws its own noise from the step's rng, fresh every
  step: `BlockDiffusionNoise` makes the 2 L ids [noisy | clean], the labels
  and the 1/t weights, and counts the masked positions;
- both comparisons are made on ONE stated draw (the rng `PRNGKey(0)` that
  `Net.apply` folds for the layer): the reference is handed the layer's tops
  and `sdar_ref.noise_faults` holds them to the definition;
- the logits are the noisy half's, (L, vocabulary); the gradient is of the
  weighted masked loss, and the loss's own value on that draw is held to
  the reference's too;
- a third comparison, `mask_probe`: the flash kernels under the block mask
  alone, forward and both backward kernels at the timed shapes, against the
  reference's dense mask on inputs where the mask decides the result. What
  a row sees inside its block is 4 keys of thousands and moves the net's
  logits and gradients by less than rounding does;
- the rows each held expert received and the masked count are read from
  the same forward pass at set-up, and once more after the window on
  another draw, never inside it;
- fresh noise every step moves a step's loss by a few per cent (the
  configuration's `checks.loss.why` states the spread), the first as much
  as any: the loss ceiling is a share of ln(vocabulary), not of the first
  reading, and holds the mean of the readings from the stated iteration
  on.

`correct` = (a) the three comparisons within the configuration's limits,
probe and gradient check before the `Solver` is built, the logits check on
the solver's fresh weights; the draw faultless and the masked share of the L
positions inside the stated range, at iteration 0 and after the window; (b)
every loss finite, and the ceiling met by the stated iteration; (c) no
program built inside the window; (d) as many Mosaic calls a step as the
configuration states.

`python3 benchmarks/drivers/train_bd_lm.py --controls [--seed n]
[--rehearse]` puts the reference with one fault planted (`faults`) in the
program's place and runs the same comparisons against the same limits:
each must come out not correct, and the reference with operands rounded to
bf16, the program's own precision, correct.
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
from reference import sdar_ref  # noqa: E402


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
NOISE = "BlockDiffusionNoise"
DRAW = ("ids", "label", "weight", "masked")   # the noise layer's tops


def stated_key():
    """The rng both comparisons hand `Net.apply`: one stated draw."""
    return jax.random.PRNGKey(0)


def make_tokens(key, batch: int, seq: int, sz: sdar_ref.Sizes) -> dict:
    """The fixed clean batch, in one jitted call on the device: every id of
    the slice but the mask id, which is its last."""
    if sz.mask_id != sz.vocab - 1:
        raise ValueError(f"mask_id {sz.mask_id} is not the slice's last id "
                         f"{sz.vocab - 1}")
    return {"tokens": jax.jit(lambda key: jax.random.randint(
        key, (batch, seq), 0, sz.mask_id, jnp.int32))(key)}


def fresh_net(cell: dict, precision: str):
    """The recipe's TRAIN net at the job's batch, as `build_job` sizes it,
    without a Solver: (net, batch, clean sequence length L)."""
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
    if set(specs) != {"tokens"}:
        raise ValueError(f"the recipe's feeds are {sorted(specs)}, not "
                         f"tokens alone")
    return net, batch, specs["tokens"][1]


def noise_draw(net, feeds: dict, rng) -> dict:
    """The noise layer's tops for `rng`, as `Net.apply` folds it: the
    layers up to and including the noise layer, nothing else."""
    upto = 1 + next(i for i, layer in enumerate(net.layers)
                    if layer.lp.type == NOISE)
    env, _, _ = net.apply_range({}, {}, feeds, {}, 0, upto, train=True,
                                rng=rng)
    return {k: env[k] for k in DRAW}


def rel_rms(got, want) -> float:
    got, want = (jnp.asarray(a, jnp.float32) for a in (got, want))
    return float(jnp.sqrt(jnp.sum((got - want) ** 2) / jnp.sum(want ** 2)))


def mask_probe(cell: dict, sz, seq: int, key, precision: str) -> dict:
    """The flash kernels under the block mask, forward and both backward
    kernels, at the timed shapes and precision, against the reference's
    dense mask on inputs where the mask decides the result
    (`sdar_ref.probe_inputs`): the largest relative RMS distance of the
    output and the three gradients, against `checks.mask_probe`."""
    from caffe_mpi_tpu.ops.attention import attention

    spec = {**cell["config"]["checks"]["mask_probe"],
            **cell["preset"].get("mask_probe", {})}
    dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32
    q, k, v, cot = jax.jit(lambda key: sdar_ref.probe_inputs(
        key, seq, sz, dtype))(key)

    @jax.jit
    def system(q, k, v, cot):
        out, vjp = jax.vjp(lambda q, k, v: attention(
            q, k, v, block_diffusion=sz.block_length, use_flash=True),
            q, k, v)
        return (out, *vjp(cot))

    want = jax.jit(lambda *a: sdar_ref.probe_reference(
        *a, sz, lm.check_specs(cell)[0]["q_block"]))(q, k, v, cot)
    return probe_verdict(system(q, k, v, cot), want, spec)


def probe_verdict(got, want, spec: dict) -> dict:
    rel = {name: rel_rms(g, w) for name, g, w
           in zip(("out", "dq", "dk", "dv"), got, want)}
    worst = max(rel, key=rel.get)
    finite = all(math.isfinite(r) for r in rel.values())
    return {"rel_rms": rel, "worst": worst, "worst_rel_rms": rel[worst],
            "rel_rms_max": spec["rel_rms_max"],
            "ok": bool(finite and rel[worst] <= spec["rel_rms_max"])}


def memory_now(devices) -> dict:
    """What `peak_hbm_gb` is made of, so far: the set-up's comparisons
    reserve more than the step does."""
    stats = devices[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "peak_bytes_reserved")}


def rows_blobs(sz: sdar_ref.Sizes) -> list[str]:
    return [f"blk{l}/moe_rows" for l in range(sz.layers)]


def reference_hidden(params, draw: dict, sz, q_block: int, **how):
    return sdar_ref.hidden(sdar_ref.from_net(params, sz), draw["ids"], sz,
                           q_block, **how).reshape(-1, sz.hidden)


def reference_loss_and_grads(params, draw: dict, sz, spec: dict, **how):
    """The reference's blocked loss on the draw and `jax.grad` of it in the
    program's blob layouts (`from_net` is linear, so it carries gradients
    back)."""
    return jax.value_and_grad(lambda p: sdar_ref.loss_blocked(
        sdar_ref.from_net(p, sz), draw["ids"], draw["label"],
        draw["weight"], sz, spec["q_block"], spec["vocab_block"],
        **how))(params)


def grads_and_loss_verdict(leaves: dict, loss: float, want_loss: float,
                           gspec: dict, precision: str) -> dict:
    """`train_lm.grads_verdict`, and the loss itself beside the
    reference's. Where two of the router's logits tie within rounding, the
    masked rows (one row to a fresh router) change expert together in bf16
    and not in float32, and an expert bank's gradient reads as all wrong:
    the gradient limits stand above that mode, so what defines the loss
    (the weights, which positions count) is held by its value."""
    limit = gspec["loss_rel_max"][precision]
    rel = abs(loss - want_loss) / abs(want_loss)
    verdict = lm.grads_verdict(leaves, gspec, precision)
    return {**verdict, "loss": loss, "reference_loss": want_loss,
            "loss_rel": rel, "loss_rel_max": limit,
            "ok": bool(verdict["ok"] and math.isfinite(loss)
                       and rel <= limit)}


def grads_check(cell: dict, net, precision: str, seed: int, feeds: dict,
                sz) -> dict:
    """The gradient the first step applies (`train_lm.grads_check`, with
    this configuration's reference and the stated draw), before the Solver
    is built: beside its state neither gradient fits on the chip."""
    _, gspec = lm.check_specs(cell)
    params, state = net.init(jax.random.PRNGKey(seed))
    rng = stated_key()
    kept = jnp.bfloat16 if precision == "bf16" else jnp.float32
    def program(p, s, f):
        loss, grads = jax.value_and_grad(
            lambda p: net.apply(p, s, f, train=True, rng=rng)[2])(p)
        return loss, jax.tree.map(lambda g: g.astype(kept), grads)
    loss, have = jax.jit(program)(params, state, feeds)
    frozen = lm.frozen_leaves(net)

    def against_the_reference(p, f, have):
        want_loss, want = reference_loss_and_grads(
            p, noise_draw(net, f, rng), sz, gspec)
        return want_loss, lm.leaf_distances(have, want, frozen)
    want_loss, leaves = jax.device_get(jax.jit(against_the_reference)(
        params, feeds, have))
    return {"frozen": sorted("/".join(k) for k in frozen),
            **grads_and_loss_verdict(leaves, float(loss), float(want_loss),
                                     gspec, precision)}


def logits_check(cell: dict, job, feeds: dict, sz):
    """Relative RMS distance between the timed net's noisy-half logits and
    the plain reference's on the timed batch, the stated draw and the
    solver's fresh weights; the draw held to the definition; the rows each
    held expert received and the masked count in that forward pass. Also
    returns the function that reads rows and count again on later weights
    and another draw (the compiled forward pass, so nothing is built after
    the window)."""
    from caffe_mpi_tpu.net import Net

    spec, _ = lm.check_specs(cell)
    net = Net(job.npar, phase="TRAIN", precision=job.precision)
    params, state = job.solver.params, job.solver.net_state
    counted = rows_blobs(sz)

    @jax.jit
    def system(params, state, feeds, rng):
        blobs, _, _ = net.apply(params, state, feeds, train=True, rng=rng)
        return (blobs["logits"].reshape(-1, sz.vocab),
                {k: blobs[k] for k in DRAW}, [blobs[b] for b in counted])

    as_lists = lambda rows: [np.asarray(r, np.float64).tolist()
                             for r in jax.device_get(rows)]

    def count(params, rng) -> tuple[list, float]:
        _, draw, rows = system(params, state, feeds, rng)
        return as_lists(rows), float(draw["masked"])

    got, draw, rows = system(params, state, feeds, stated_key())
    x = jax.jit(lambda p, d: reference_hidden(p, d, sz, spec["q_block"]))(
        params, draw)
    num, den, finite = jax.device_get(jax.jit(
        lambda p, x, got: lm.logits_distance(
            p["logits"]["weight"], x, got, spec["vocab_block"]))(
                params, x, got))
    noise = sdar_ref.noise_faults(feeds["tokens"], draw["ids"],
                                  draw["label"], draw["weight"], sz)
    seq = feeds["tokens"].shape[1]
    verdict = lm.logits_verdict(num, den, finite, got.size,
                                spec["rel_rms_max"][job.precision])
    noise_ok = not any(v for k, v in noise.items() if k != "masked_share") \
        and float(draw["masked"]) == round(noise["masked_share"]
                                           * feeds["tokens"].size)
    return count, {
        "blob": "logits", "phase": "TRAIN", "rows_of": "the noisy half",
        "sequences": got.shape[0] // seq, "seq_len": seq, **verdict,
        "noise": noise, "noise_ok": bool(noise_ok),
        "masked": float(draw["masked"]), "bd_rows": as_lists(rows),
        "ok": bool(verdict["ok"] and noise_ok)}


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
    sz = sdar_ref.sizes_from_config(config, preset)
    net, batch, seq = fresh_net(cell, traffic["precision"])
    if not preset and seq != traffic["seq_len"]:
        raise ValueError(f"the recipe's sequence length {seq} is not the "
                         f"mix's seq_len {traffic['seq_len']}")
    key = jax.random.PRNGKey(seed)
    feeds = make_tokens(jax.random.fold_in(key, 1), batch, seq, sz)
    probe = mask_probe(cell, sz, seq, jax.random.fold_in(key, 2),
                       traffic["precision"])
    say(check="mask_probe", **probe)
    grads = grads_check(cell, net, traffic["precision"], seed, feeds, sz)
    say(check="grads", **grads, device_memory=memory_now(devices))
    t_grads = clock()
    job = train.build_job(cell, seed, out_dir, devices)
    solver, block = job.solver, job.block
    t_built = clock()
    try:
        if job.batch != batch:
            raise ValueError(f"the job's batch {job.batch} is not {batch}")
        count, logits = logits_check(cell, job, feeds, sz)
        say(check="logits", **logits, device_memory=memory_now(devices))
        t_checked = clock()
        seen = train.measure(job, lambda it: feeds, seconds=seconds,
                             trace_spec=traffic["trace"] if trace else None,
                             out_dir=out_dir, counter=counter)
        # after the window, on another draw: the routers are frozen, so
        # routing moves only as far as the other weights' training and the
        # draw move the routers' inputs
        rows_after, masked_after = count(
            solver.params, jax.random.PRNGKey(solver.iter))
    finally:
        solver.close()
    masked = {"at_iteration_0": logits["masked"] / (batch * seq),
              "after_the_window": masked_after / (batch * seq)}
    lo, hi = {**config["checks"], **preset}["masked_share"]["range"]
    masked_ok = all(lo <= share <= hi for share in masked.values())
    say(check="masked_share", **masked, range=[lo, hi], ok=masked_ok,
        bd_rows_at_iteration_0=[sum(r) for r in logits["bd_rows"]],
        bd_rows_after_the_window=[sum(r) for r in rows_after],
        iteration=solver.iter)
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
    # every step draws its own noise, so one reading swings by a few per
    # cent: the readings from the stated iteration on, averaged
    late = sum(loss for _, loss in reached) / max(len(reached), 1)
    loss_ok = bool(reached) and late <= ceiling
    say(check="loss", first=losses[0][1], ln_vocab=math.log(sz.vocab),
        reached=reached, mean_of_reached=late, last=losses[-1],
        ceiling=ceiling,
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
        rows_per_sample=2 * seq, tokens_per_s=rate * seq,
        step_ms_median=med, step_ms_q1=q1, step_ms_q3=q3,
        block_s=[b["s"] for b in blocks], block_end_syncs=len(blocks),
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
        "macs_per_sample": sdar_ref.macs_per_sample(sz, seq),
        "bd_sizes": sdar_ref.sizes_record(sz),
        "bd_rows": logits["bd_rows"], "bd_rows_after": rows_after,
        "masked_share": masked,
        "step_ms": {"q1": q1, "median": med, "q3": q3},
        "logits": logits, "grads": grads, "mask_probe": probe,
        "losses": losses, **counts,
    }
    return {
        "correct": bool(logits["ok"] and grads["ok"] and probe["ok"]
                        and masked_ok and loss_ok and nonfinite == 0
                        and seen["compiles_in_window"] == 0 and pallas_ok),
        "attempted": iters,
        "failed": seen["skipped_steps"] + nonfinite,
        "end_to_end": {"train_samples_per_s": rate, "setup_s": setup_s},
        "record": record,
        "trace": summary,
    }


# -- controls: the reference with one fault planted, in the program's place --

def faults() -> dict:
    """name -> (sound?, keyword arguments of the reference, a fault of the
    loss alone?)."""
    masks = {f"mask_{m}": (False, {"mask": m}, False)
             for m in sdar_ref.MASKS[1:]}
    return {
        "operands_bf16": (True, {"operand_dtype": jnp.bfloat16}, False),
        "operands_f8_e4m3": (False, {"operand_dtype": jnp.float8_e4m3fn},
                             False),
        **masks,
        "positions_0_to_2L": (False, {"positions": "absolute"}, False),
        "no_qk_norm": (False, {"qk_norm": False}, False),
        "weights_dropped": (False, {"loss": "unweighted"}, True),
        "loss_over_all_positions": (False, {"loss": "all_positions"}, True),
    }


def controls(cell: dict, seed: int, say) -> bool:
    """The set-up's two comparisons, against the same limits, with the
    reference under each planted fault where the program stands. True if
    every fault came out not correct BY AT LEAST ONE LIMIT and the sound
    control correct by both."""
    cell = lm.with_preset(cell)
    config, traffic, preset = cell["config"], cell["traffic"], cell["preset"]
    sz = sdar_ref.sizes_from_config(config, preset)
    net, batch, seq = fresh_net(cell, "f32")
    key = jax.random.PRNGKey(seed)
    params, _ = net.init(key)
    feeds = make_tokens(jax.random.fold_in(key, 1), batch, seq, sz)
    draw = jax.jit(lambda f: noise_draw(net, f, stated_key()))(feeds)
    spec, gspec = lm.check_specs(cell)
    precision = traffic["precision"]
    chosen = faults()
    passed = {name: [] for name in chosen}

    hidden = lambda **how: jax.jit(
        lambda p, d: reference_hidden(p, d, sz, spec["q_block"], **how))(
            params, draw)
    logits = lambda x, dt: jax.jit(lambda p, x: sdar_ref.logits_block(
        {"head": jnp.asarray(p["logits"]["weight"], jnp.float32).T}, x, 0,
        sz.vocab, dt))(params, x)
    distance = jax.jit(lambda p, x, got: lm.logits_distance(
        p["logits"]["weight"], x, got, spec["vocab_block"]))
    probe_spec = {**config["checks"]["mask_probe"],
                  **preset.get("mask_probe", {})}
    inputs = jax.jit(lambda key: sdar_ref.probe_inputs(
        key, seq, sz, jnp.bfloat16 if precision == "bf16" else jnp.float32))(
            jax.random.fold_in(key, 2))
    probed = lambda mask: jax.jit(lambda *a: sdar_ref.probe_reference(
        *a, sz, spec["q_block"], mask))(*inputs)
    want = probed(sdar_ref.MASKS[0])
    for name, (sound, how, _) in chosen.items():
        if "mask" in how:
            verdict = probe_verdict(probed(how["mask"]), want, probe_spec)
            passed[name].append(verdict["ok"])
            say(control="mask_probe", fault=name, sound=sound,
                correct=verdict["ok"], **verdict)
    del want, inputs
    x = hidden()
    for name, (sound, how, loss_only) in chosen.items():
        if loss_only:
            continue
        got = logits(hidden(**how), how.get("operand_dtype"))
        verdict = lm.logits_verdict(
            *jax.device_get(distance(params, x, got)), got.size,
            spec["rel_rms_max"][precision])
        passed[name].append(verdict["ok"])
        say(control="logits", fault=name, sound=sound, correct=verdict["ok"],
            **verdict)
    del x, got

    grad = lambda **how: jax.jit(
        lambda p, d: reference_loss_and_grads(p, d, sz, gspec, **how))(
            params, draw)
    frozen = lm.frozen_leaves(net)
    distance = jax.jit(lambda have, want: lm.leaf_distances(have, want,
                                                            frozen))
    want_loss, want = grad()
    for name, (sound, how, _) in chosen.items():
        loss, have = grad(**how)
        verdict = grads_and_loss_verdict(
            jax.device_get(distance(have, want)), float(loss),
            float(want_loss), gspec, precision)
        del have   # 2.2 GB at the timed size, beside the next one's
        passed[name].append(verdict["ok"])
        say(control="grads", fault=name, sound=sound, correct=verdict["ok"],
            **verdict)
    # a fault is caught if at least one limit refuses it; the sound control
    # has to pass every one
    as_expected = all(all(passed[name]) == sound
                      for name, (sound, _, _) in chosen.items())
    say(controls_as_expected=bool(as_expected),
        passed_a_limit=sorted(name for name, oks in passed.items()
                              if any(oks) and not chosen[name][0]))
    return bool(as_expected)


def main(argv: list[str]) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=controls.__doc__)
    ap.add_argument("--controls", action="store_true", required=True)
    ap.add_argument("--workload", default="sdar_bf16_s8k_bd4_ep8share")
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
