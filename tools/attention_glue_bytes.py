#!/usr/bin/env python3
"""What one attention layer, or one hyper-connection, moves through HBM
beside its kernels and its matrix products, counted without a chip.

    JAX_PLATFORMS=cpu python3 tools/attention_glue_bytes.py <prototxt> <layer name> [--precision bf16]
    JAX_PLATFORMS=cpu python3 tools/attention_glue_bytes.py <prototxt> <HyperConnectionPre name> <its HyperConnectionPost name>

Builds the net of `<prototxt>`, takes the one `Attention` layer named, and
compiles its forward and backward pass (`jax.vjp` of `layer.apply` over the
parameters and the bottom, under the layer's own `remat` as `Net` applies
it) for a described `v5e:2x2` with nothing attached. Given a
`HyperConnectionPre` and its `HyperConnectionPost`, it compiles the pair
the same way, each under its own `remat`, with an elementwise stand-in for
the sub-layer between them (its input times 2): `jax.vjp` over the Pre
layer's parameters and the stream. Over the operations of the optimized
entry computation it sums operand bytes + result bytes in three classes:

  mosaic       the flash kernels (`tpu_custom_call`)
  projections  the fusions that hold a convolution (XLA:TPU's matrix product)
  glue         everything else: loop fusions, copies, reductions

and prints them with every glue operation over 32 MB. Bytes and counts from
the compiler's text, never a time: nothing runs. The count is static, so it
is this script and a test (`tests/test_tpu_aot_compile.py`), not a span.

The xing cell's `blk1/hc1` + `blk1/hc1_out` (4,096 tokens, a stream of
4 x 3,584 in bf16): 9.50 GB with jax's own backward passes over the
(N, S, n, C) view (float32 copies of the stream, its relayouts, the
broadcast mix), 4.56 GB with the hand-written ones over the flat view
(`layers/hyper_connection.py`), of which ~1.6 GB are the stream's relayouts
at this harness's own argument and result; `xing_ref.mhc_cost` counts
1.205 GB by the definition.
"""

from __future__ import annotations

import argparse
import math
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:  # run as a script
    sys.path.insert(0, _ROOT)

_ITEM = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
         "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
         "u64": 8}
_ARRAY = re.compile(r"\b(%s)\[([\d,]*)\]" % "|".join(_ITEM))
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = (.*?) ([\w-]+)\((.*)$")
# operations that move nothing: views, tuples, what the caller hands over
_FREE = {"parameter", "tuple", "get-tuple-element", "bitcast", "constant",
         "after-all", "partition-id", "replica-id"}


def _arrays(shape_text: str) -> list[tuple[str, int]]:
    """(dtype, elements) of every array in a shape's text, tuples
    flattened."""
    return [(dtype, math.prod(int(d) for d in dims.split(",") if d))
            for dtype, dims in _ARRAY.findall(shape_text)]


def _bytes(shape_text: str) -> int:
    return sum(_ITEM[dtype] * n for dtype, n in _arrays(shape_text))


def classify(text: str) -> list[dict]:
    """One record an operation of the entry computation of a compiled
    module's text that touches HBM: name, opcode, kind (mosaic |
    projections | glue), bytes (operands + results), result (its shape's
    text)."""
    computations = re.split(r"\n(?=(?:ENTRY )?%[\w.-]+ \()", text)
    holds_product = {
        re.match(r"(?:ENTRY )?%([\w.-]+)", c).group(1)
        for c in computations
        if re.match(r"(?:ENTRY )?%", c) and " convolution(" in c}
    entry = next(c for c in computations if c.startswith("ENTRY "))
    shapes, records = {}, []
    for line in entry.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, shape, opcode, rest = m.groups()
        shapes[name] = shape
        # an asynchronous pair is counted at its end
        if (opcode in _FREE or opcode.endswith("-start")
                or 'custom_call_target="ConcatBitcast"' in rest):
            continue
        operands = re.findall(r"%([\w.-]+)", rest.split("), ")[0])
        moved = _bytes(shape) + sum(_bytes(shapes.get(o, ""))
                                    for o in operands)
        if opcode.endswith("-done"):
            moved = 2 * _bytes(shape)       # read once, written once
        called = re.search(r"calls=%([\w.-]+)", rest)
        if 'custom_call_target="tpu_custom_call"' in rest:
            kind = "mosaic"
        elif opcode == "convolution" or (called
                                         and called.group(1) in holds_product):
            kind = "projections"
        else:
            kind = "glue"
        records.append({"name": name, "opcode": opcode, "kind": kind,
                        "bytes": moved, "result": shape})
    return records


def totals(records: list[dict]) -> dict[str, int]:
    out = {"mosaic": 0, "projections": 0, "glue": 0}
    for r in records:
        out[r["kind"]] += r["bytes"]
    return out


def widest_f32_glue_result(records: list[dict]) -> int:
    """Elements of the largest float32 array a glue operation writes."""
    return max((n for r in records if r["kind"] == "glue"
                for dtype, n in _arrays(r["result"]) if dtype == "f32"),
               default=0)


def _applied(layer):
    """fn(params, bottoms) -> tops of one layer in training, under
    `jax.checkpoint` where the prototxt says `remat: true`, as
    `Net.apply_range` applies it."""
    import jax
    apply = lambda p, xs: layer.apply(p, {}, list(xs), train=True,
                                      rng=None)[0]
    if layer.lp.remat:
        apply = jax.checkpoint(
            apply, policy=jax.checkpoint_policies.save_only_these_names(
                *layer.kept_under_remat))
    return apply


def _abstract_params(layer) -> dict:
    import jax
    return {name: jax.ShapeDtypeStruct(
        decl.shape, decl.dtype if decl.dtype is not None
        else layer.policy.master) for name, decl in layer.params.items()}


def _abstract_bottoms(layer) -> tuple:
    import jax
    master, compute = layer.policy.master, layer.policy.cast_in
    return tuple(jax.eval_shape(compute, jax.ShapeDtypeStruct(s, master))
                 for s in layer.in_shapes)


def layer_vjp(net, layer_name: str):
    """(fn, its arguments as ShapeDtypeStructs) of one layer's forward +
    backward pass: fn(params, bottoms, cotangent) -> (top, gradients),
    under the layer's own `remat`. The top has the first bottom's
    shape."""
    import jax

    layer = next(l for l in net.layers if l.name == layer_name)
    if layer.type_name != "Attention":
        raise SystemExit(f"{layer_name!r} is a {layer.type_name} layer")
    apply = _applied(layer)

    def fn(params, xs, dy):
        y, vjp = jax.vjp(lambda p, xs: apply(p, xs)[0], params, xs)
        return y, vjp(dy)

    bottoms = _abstract_bottoms(layer)
    return fn, (_abstract_params(layer), bottoms, bottoms[0])


def pair_vjp(net, pre_name: str, post_name: str):
    """(fn, its arguments) of one hyper-connection's forward + backward
    pass: the `HyperConnectionPre` named, a stand-in sub-layer (its output
    times 2, elementwise) and the `HyperConnectionPost` named, each under
    its own `remat`: fn(params, stream, cotangent) -> (new stream,
    gradients of the parameters and the stream)."""
    import jax

    pre, post = (next(l for l in net.layers if l.name == name)
                 for name in (pre_name, post_name))
    if (pre.type_name, post.type_name) != ("HyperConnectionPre",
                                           "HyperConnectionPost"):
        raise SystemExit(f"{pre_name!r}, {post_name!r} are a "
                         f"{pre.type_name} and a {post.type_name} layer")
    pre_apply, post_apply = _applied(pre), _applied(post)

    def pair(params, x):
        mixed, coef = pre_apply(params, (x,))
        return post_apply({}, (x, 2 * mixed, coef))[0]

    def fn(params, x, dout):
        out, vjp = jax.vjp(pair, params, x)
        return out, vjp(dout)

    x = _abstract_bottoms(pre)[0]
    return fn, (_abstract_params(pre), x, x)


def compiled_text(fn, args, device=None) -> str:
    """`fn` compiled for one described v5e chip (`device`, or the first of
    a `v5e:2x2` described here); `args` a tree of ShapeDtypeStructs."""
    import jax
    from jax.sharding import SingleDeviceSharding
    if device is None:
        from jax.experimental import topologies
        device = topologies.get_topology_desc(
            topology_name="v5e:2x2", platform="tpu").devices[0]
    chip = SingleDeviceSharding(device)
    placed = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=chip), args)
    return (jax.jit(fn).trace(*placed)
            .lower(lowering_platforms=("tpu",)).compile().as_text())


def layer_records(prototxt: str, layer_name: str, precision: str = "bf16",
                  device=None, post: str | None = None) -> list[dict]:
    """`classify` of one attention layer's compiled pass, or with `post`
    of the hyper-connection pair `layer_name` / `post`."""
    from caffe_mpi_tpu.net import Net
    from caffe_mpi_tpu.proto import NetParameter
    with open(prototxt) as f:
        net = Net(NetParameter.from_text(f.read()), precision=precision,
                  model_dir=os.path.dirname(prototxt))
    fn, args = (pair_vjp(net, layer_name, post) if post
                else layer_vjp(net, layer_name))
    return classify(compiled_text(fn, args, device))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("prototxt")
    ap.add_argument("layer")
    ap.add_argument("post", nargs="?",
                    help="with a HyperConnectionPre as `layer`: its "
                         "HyperConnectionPost, compiled as one pair")
    ap.add_argument("--precision", default="bf16", choices=("f32", "bf16"))
    ap.add_argument("--over-mb", type=float, default=32.0,
                    help="list glue operations that move more than this")
    args = ap.parse_args(argv)
    records = layer_records(args.prototxt, args.layer, args.precision,
                            post=args.post)
    gb = lambda n: f"{n / 1e9:.2f} GB"
    what = f"{args.layer} + {args.post}" if args.post else args.layer
    print(f"{what} of {args.prototxt}, forward + backward, "
          f"{args.precision}, one described v5e chip (a count, not a time)")
    by_kind = totals(records)
    for kind, n in by_kind.items():
        ops = sum(r["kind"] == kind for r in records)
        print(f"  {kind:12s} {gb(n):>9s}  in {ops} operations")
    print(f"  {'all':12s} {gb(sum(by_kind.values())):>9s}")
    by_opcode = {}
    for r in records:
        if r["kind"] == "glue":
            by_opcode[r["opcode"]] = by_opcode.get(r["opcode"], 0) + r["bytes"]
    print("  glue by opcode: " + ", ".join(
        f"{op} {gb(n)}" for op, n in sorted(by_opcode.items(),
                                            key=lambda kv: -kv[1])))
    print(f"  largest float32 result of a glue operation: "
          f"{widest_f32_glue_result(records):,} elements")
    for r in sorted(records, key=lambda r: -r["bytes"]):
        if r["kind"] == "glue" and r["bytes"] > args.over_mb * 1e6:
            shape = re.sub(r"\{[^}]*\}", "", r["result"])
            print(f"  {r['bytes'] / 1e6:8.1f} MB  {r['name']} "
                  f"({r['opcode']}) -> {shape[:100]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
