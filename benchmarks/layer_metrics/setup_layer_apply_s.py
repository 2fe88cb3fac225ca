"""Python seconds inside the layers' `apply`, all layer types, summed by
`spans.layer_scope` wherever a net is traced (by type on the earlier
line): the layers' own share of setup_trace_s; the rest of that is
autodiff's transposes and jit's machinery. Over the whole process up to the
read: nothing is traced after set-up. Layer: Net_layers. Moves setup_s."""

import startup_reduce


def compute(run: dict, trace: dict | None):
    return startup_reduce.metric(run, "setup_layer_apply_s")
