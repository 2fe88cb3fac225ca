#!/usr/bin/env python
"""Deviceless MFU analysis: AOT cost analysis + roofline for the bench
configs (the task: explain 'where the 84% goes' without hardware). Its
tables are XLA's CPU lowering — hypotheses for the chip, not a roofline
(ROADMAP, "CPU-derived tables").

For each (model, batch, dtype) bench config this compiles the FULL train
step ahead-of-time on the CPU backend (the flop/byte counts come from
XLA's HloCostAnalysis over the optimized module — architecture-neutral),
then combines them with the v5e roofline:

    peak      = 197 TFLOP/s (bf16 MXU),  HBM BW = 819 GB/s
    ridge AI  = 197e12 / 819e9  ~ 240 FLOP/byte
    bw-bound MFU ceiling = min(1, AI / ridge)

The gap decomposition is written to docs/mfu_analysis.md.

Also resolves the NHWC conv layout A/B (CAFFE_CONV_LAYOUT knob,
ops/conv.py): compiles the AlexNet step both ways and diffs the optimized
HLO op mix (transpose count, flops, bytes). CPU layout assignment is not
TPU's — the diff measures what the emulation ADDS, the hardware knob
stays for a live A/B — but if XLA already cancels the edge transposes on
CPU, the NCHW default is safe.

Usage: [JAX_PLATFORMS=cpu] python tools/mfu_analysis.py [--quick]
Writes docs/mfu_analysis.md + docs/mfu_analysis.json.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

V5E_PEAK = 197e12     # bf16 MXU FLOP/s (utils/flops.py table)
V5E_HBM = 819e9       # bytes/s
RIDGE = V5E_PEAK / V5E_HBM

# (key, solver, batch, note[, precision])
# the *_knob_bf16 rows exercise ISSUE 9's `precision: bf16` solver knob
# on the STOCK f32 prototxts (one-knob bf16, vs the hand-written fp16
# prototxt variants of the older rows) — the f32/knob-bf16 pairs are the
# roofline-ceiling delta the precision section of docs/benchmarks.md
# quotes
CONFIGS = [
    ("alexnet_b256_f32", "models/alexnet/solver.prototxt", 256,
     "headline bench config"),
    ("alexnet_b256_bf16", "models/alexnet/solver_fp16.prototxt", 256,
     "staged headline config, not yet measured on the chip"),
    ("alexnet_b256_knob_bf16", "models/alexnet/solver.prototxt", 256,
     "ISSUE 9 precision knob: stock prototxt + precision bf16", "bf16"),
    ("resnet50_b32_f32", "models/resnet50/solver.prototxt", 32,
     "reference per-GPU batch"),
    ("resnet50_b256_bf16", "models/resnet50/solver_fp16.prototxt", 256,
     "north-star config: DGX-1-recipe batch, bf16 storage"),
    ("resnet50_b256_knob_bf16", "models/resnet50/solver.prototxt", 256,
     "ISSUE 9 precision knob: stock prototxt + precision bf16", "bf16"),
]


def _pin_cpu():
    import jax
    jax.config.update("jax_platforms", "cpu")
    assert jax.devices()[0].platform == "cpu"


def build_step(solver_path: str, batch: int, precision: str = ""):
    """Build the Solver and return (lowered-args, jitted step, net)."""
    import jax
    import jax.numpy as jnp
    from caffe_mpi_tpu.proto import NetParameter, SolverParameter
    from caffe_mpi_tpu.solver import Solver

    from caffe_mpi_tpu.utils.model_shapes import input_shapes

    sp = SolverParameter.from_file(os.path.join(_ROOT, solver_path))
    sp.max_iter = 10**9
    sp.display = 0
    sp.snapshot = 0
    sp.test_interval = 0
    if precision:
        sp.precision = precision
        # static scale: the AOT cost analysis wants the plain program,
        # not the guard/cond the dynamic schedule adds
        sp.loss_scale = 128.0
    npar = NetParameter.from_file(os.path.join(_ROOT, sp.net))
    shapes = input_shapes(npar, batch=batch)
    sp.net = ""
    sp.net_param = npar
    solver = Solver(sp, model_dir=_ROOT)
    step = solver._build_step()

    # abstract feeds: AOT never materializes the batch. Integer tops are
    # detected structurally (1-D bottom of a classification loss), same
    # rule as synthetic_feeds — not by the literal name 'label'
    from caffe_mpi_tpu.utils.model_shapes import label_tops
    ints = label_tops(npar, shapes)
    feeds = {}
    for top, dims in shapes.items():
        if top in ints:
            feeds[top] = jax.ShapeDtypeStruct((1, dims[0]), jnp.int32)
        else:
            feeds[top] = jax.ShapeDtypeStruct((1, *dims), jnp.float32)
    args = (solver.params, solver.net_state, solver.opt_state, feeds,
            jnp.int32(0), jax.random.PRNGKey(0))
    return args, step, solver.net


def layer_roofline(net, batch: int, act_bytes: int) -> list[dict]:
    """Analytic per-layer roofline ranking — the 'worst bf16 offenders'
    list (ISSUE 9). For each layer: fwd+bwd FLOPs from the MAC model
    (utils/flops.py; 2x fwd for the backward, the usual conv
    approximation) and HBM traffic from blob/param sizes at the compute
    dtype (fwd: read bottoms + write tops; bwd: read bottoms + tops'
    cotangents + write bottom cotangents ~ 2x fwd; params at f32).
    est_us = max(compute, bandwidth) time on the v5e roofline; layers
    with AI below the ridge are bandwidth-bound — at bf16 the convs
    speed up toward MXU peak and these become the binding constraint,
    which is the ranking that picked LRN for the Pallas kernels
    (ops/lrn.py)."""
    from caffe_mpi_tpu.utils.flops import layer_macs_per_image
    rows = []
    for layer in net.layers:
        if not layer.lp.bottom and not layer.params:
            continue  # input layers: no compute
        flops = 2 * layer_macs_per_image(layer) * batch * 3  # fwd+bwd
        n_in = sum(_numel(net.blob_shapes.get(b, ()))
                   for b in layer.lp.bottom)
        n_out = sum(_numel(s) for s in layer.out_shapes)
        param_b = sum(_numel(d.shape) * 4 for d in layer.params.values())
        byt = (n_in + n_out) * act_bytes * 3 + param_b * 2
        if not byt and not flops:
            continue
        ai = flops / byt if byt else float("inf")
        est_us = max(flops / V5E_PEAK, byt / V5E_HBM) * 1e6
        rows.append({
            "layer": layer.name, "type": layer.lp.type,
            "gflops": round(flops / 1e9, 2),
            "mb_touched": round(byt / 2**20, 1),
            "ai": round(ai, 1),
            "bound": "bw" if ai < RIDGE else "compute",
            "est_us": round(est_us, 1),
        })
    rows.sort(key=lambda r: -r["est_us"])
    return rows


def _numel(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def analyze(key: str, solver_path: str, batch: int, note: str,
            precision: str = "") -> dict:
    import jax
    from caffe_mpi_tpu.utils.flops import train_flops_per_image

    t0 = time.time()
    args, step, net = build_step(solver_path, batch, precision)
    lowered = step.lower(*args)
    compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # jax-version drift: list of one
        cost = cost[0] if cost else {}
    mem = {}
    try:
        m = compiled.memory_analysis()
        mem = {"temp_bytes": getattr(m, "temp_size_in_bytes", None),
               "argument_bytes": getattr(m, "argument_size_in_bytes", None),
               "output_bytes": getattr(m, "output_size_in_bytes", None)}
    except Exception:
        pass
    hlo = compiled.as_text()
    flops = float(cost.get("flops", 0.0))
    byt = float(cost.get("bytes accessed", 0.0))
    analytic = train_flops_per_image(net) * batch
    ai = flops / byt if byt else None
    ceiling = min(1.0, ai / RIDGE) if ai else None
    rec = {
        "config": key, "batch": batch, "note": note,
        "analytic_model_flops_per_step": analytic,
        "xla_cost_flops_per_step": flops,
        "xla_bytes_accessed_per_step": byt,
        "arithmetic_intensity_flops_per_byte":
            round(ai, 1) if ai else None,
        "v5e_bw_bound_mfu_ceiling": round(ceiling, 4) if ceiling else None,
        "hlo_fusions": hlo.count(" fusion("),
        "hlo_convolutions": hlo.count(" convolution("),
        "hlo_transposes": hlo.count(" transpose("),
        "hlo_all_reduces": hlo.count(" all-reduce("),
        "compile_s": round(time.time() - t0, 1),
        **mem,
    }
    # per-layer offender ranking rides every config row; the bf16 rows
    # are the ranking that motivates the Pallas kernels
    act_bytes = 2 if "bf16" in key else 4
    rec["top_offenders"] = layer_roofline(net, batch, act_bytes)[:8]
    return rec


def lrn_pallas_ab() -> dict:
    """Before/after for the ops/lrn.py Pallas kernels (ISSUE 9): compile
    the AlexNet `precision: bf16` step with the stock lax LRN
    (CAFFE_LRN_PALLAS=0) and with the kernels engaged (=1), and diff
    XLA's flop/byte counts + HLO op mix. Subprocess per variant (the
    knob is read at trace time; a fresh interpreter keeps the two
    compiles honest). On CPU the kernel runs in interpreter mode — the
    diff measures graph structure (reduce-window passes removed), the
    hardware win needs a live-TPU bench round."""
    out = {}
    for knob, label in (("0", "lax"), ("1", "pallas")):
        env = {k: v for k, v in os.environ.items()
               if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
        env.update(JAX_PLATFORMS="cpu", CAFFE_LRN_PALLAS=knob)
        code = (
            "import sys; sys.path.insert(0, %r)\n"
            "import json\n"
            "from tools.mfu_analysis import build_step\n"
            "args, step, net = build_step('models/alexnet/solver.prototxt',"
            " 64, precision='bf16')\n"
            "c = step.lower(*args).compile()\n"
            "cost = c.cost_analysis() or {}\n"
            "if isinstance(cost, (list, tuple)):\n"
            "    cost = cost[0] if cost else {}\n"
            "hlo = c.as_text()\n"
            "print(json.dumps({'flops': cost.get('flops'),\n"
            "                  'bytes': cost.get('bytes accessed'),\n"
            "                  'reduce_windows': hlo.count('reduce-window'),\n"
            "                  'fusions': hlo.count(' fusion(')}))\n"
            % _ROOT)
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=900,
                           cwd=_ROOT)
        if r.returncode != 0:
            out[label] = {"error": r.stderr.strip()[-300:]}
        else:
            out[label] = json.loads(r.stdout.strip().splitlines()[-1])
    return out


def nhwc_ab() -> dict:
    """Compile the AlexNet step both conv-layout ways (subprocess per
    variant: the knob is read at ops/conv.py import) and diff the HLO."""
    out = {}
    for layout in ("NCHW", "NHWC"):
        env = {k: v for k, v in os.environ.items()
               if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
        env.update(JAX_PLATFORMS="cpu",
                   CAFFE_CONV_LAYOUT="" if layout == "NCHW" else "NHWC")
        code = (
            "import sys; sys.path.insert(0, %r)\n"
            "import json\n"
            "from tools.mfu_analysis import build_step\n"
            "args, step, net = build_step('models/alexnet/solver.prototxt', 64)\n"
            "c = step.lower(*args).compile()\n"
            "cost = c.cost_analysis() or {}\n"
            "if isinstance(cost, (list, tuple)):\n"
            "    cost = cost[0] if cost else {}\n"
            "hlo = c.as_text()\n"
            "print(json.dumps({'flops': cost.get('flops'),\n"
            "                  'bytes': cost.get('bytes accessed'),\n"
            "                  'transposes': hlo.count(' transpose('),\n"
            "                  'fusions': hlo.count(' fusion(')}))\n"
            % _ROOT)
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=900,
                           cwd=_ROOT)
        if r.returncode != 0:
            out[layout] = {"error": r.stderr.strip()[-300:]}
        else:
            out[layout] = json.loads(r.stdout.strip().splitlines()[-1])
    return out


MD_HEADER = """# MFU analysis (AOT, no hardware needed)

Generated by `tools/mfu_analysis.py` on the CPU backend: XLA
HloCostAnalysis flop/byte counts for the FULL jitted train step of each
bench config, against the v5e roofline (197 bf16 TFLOP/s, 819 GB/s HBM,
ridge ~240 FLOP/byte). See the bottom for the measured-vs-ceiling gap
decomposition and the staged hardware configs.
"""


def main() -> int:
    _pin_cpu()
    quick = "--quick" in sys.argv
    configs = CONFIGS[:1] if quick else CONFIGS
    rows = []
    for cfg in configs:
        key, path, batch, note = cfg[:4]
        precision = cfg[4] if len(cfg) > 4 else ""
        print(f"analyzing {key} ...", flush=True)
        try:
            rows.append(analyze(key, path, batch, note, precision))
            print(f"  done in {rows[-1]['compile_s']}s", flush=True)
        except Exception as e:  # keep the sweep alive; record the failure
            rows.append({"config": key, "error": repr(e)[:300]})
            print(f"  FAILED: {e!r}", flush=True)
    ab = None
    lrn_ab = None
    if not quick:
        print("NHWC A/B ...", flush=True)
        ab = nhwc_ab()
        print("LRN Pallas A/B ...", flush=True)
        lrn_ab = lrn_pallas_ab()

    payload = {"rows": rows, "nhwc_ab": ab, "lrn_pallas_ab": lrn_ab,
               "v5e": {"peak_flops": V5E_PEAK, "hbm_bytes_per_s": V5E_HBM,
                       "ridge_flops_per_byte": round(RIDGE, 1)}}
    with open(os.path.join(_ROOT, "docs/mfu_analysis.json"), "w") as f:
        json.dump(payload, f, indent=1)

    lines = [MD_HEADER]
    lines.append("| config | batch | model GFLOP/step | XLA GFLOP/step | "
                 "GB touched/step | AI (F/B) | bw-bound MFU ceiling | "
                 "convs | fusions | transposes |")
    lines.append("|---|---|---|---|---|---|---|---|---|---|")
    for r in rows:
        if "error" in r:
            lines.append(f"| {r['config']} | — | FAILED: {r['error']} "
                         "| | | | | | | |")
            continue
        lines.append(
            f"| {r['config']} | {r['batch']} "
            f"| {r['analytic_model_flops_per_step'] / 1e9:.1f} "
            f"| {r['xla_cost_flops_per_step'] / 1e9:.1f} "
            f"| {r['xla_bytes_accessed_per_step'] / 1e9:.2f} "
            f"| {r['arithmetic_intensity_flops_per_byte']} "
            f"| {r['v5e_bw_bound_mfu_ceiling']:.0%} "
            f"| {r['hlo_convolutions']} | {r['hlo_fusions']} "
            f"| {r['hlo_transposes']} |")
    # ISSUE 9: bf16 roofline offender ranking (the list the Pallas
    # kernels attack) off the precision-knob AlexNet row
    knob_row = next((r for r in rows
                     if r.get("config") == "alexnet_b256_knob_bf16"
                     and "top_offenders" in r), None)
    if knob_row:
        lines.append("\n## bf16 roofline offenders "
                     "(alexnet_b256 @ precision: bf16, analytic)\n")
        lines.append("Per-layer fwd+bwd roofline estimate at bf16 "
                     "activations; `bound=bw` layers cannot reach MXU "
                     "peak no matter the dtype — the top bandwidth-bound "
                     "entries are the Pallas kernel targets "
                     "(ops/lrn.py shipped for LRN; pooling is next).\n")
        lines.append("| layer | type | GFLOP | MiB touched | AI | bound "
                     "| est us |")
        lines.append("|---|---|---|---|---|---|---|")
        for o in knob_row["top_offenders"]:
            lines.append(
                f"| {o['layer']} | {o['type']} | {o['gflops']} "
                f"| {o['mb_touched']} | {o['ai']} | {o['bound']} "
                f"| {o['est_us']} |")
    if lrn_ab:
        lines.append("\n## LRN Pallas kernel before/after "
                     "(AlexNet b64 @ precision: bf16, CPU HLO diff)\n")
        lines.append("| variant | XLA GFLOP | GB touched | reduce-windows "
                     "| fusions |")
        lines.append("|---|---|---|---|---|")
        for kname, v in lrn_ab.items():
            if "error" in v:
                lines.append(f"| {kname} | FAILED {v['error']} | | | |")
            else:
                lines.append(f"| {kname} | {v['flops'] / 1e9:.1f} "
                             f"| {v['bytes'] / 1e9:.2f} "
                             f"| {v['reduce_windows']} | {v['fusions']} |")
    if ab:
        lines.append("\n## NHWC conv-layout A/B (CPU HLO diff, AlexNet b64)\n")
        lines.append("| layout | XLA GFLOP | GB touched | transposes | fusions |")
        lines.append("|---|---|---|---|---|")
        for k, v in ab.items():
            if "error" in v:
                lines.append(f"| {k} | FAILED {v['error']} | | | |")
            else:
                lines.append(f"| {k} | {v['flops'] / 1e9:.1f} "
                             f"| {v['bytes'] / 1e9:.2f} | {v['transposes']} "
                             f"| {v['fusions']} |")
    with open(os.path.join(_ROOT, "docs/mfu_analysis.md"), "w") as f:
        f.write("\n".join(lines) + "\n")
    print("wrote docs/mfu_analysis.{md,json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
