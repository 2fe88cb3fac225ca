"""Prototxt parse and `Net` construction in set-up: the ledger's `parse` and
`net/build` phases, each counted where it lies inside no other of the two,
every `Net` built (the solver's and the checks'). Host seconds. Layer:
CLI_launch. Moves setup_s."""

import startup_reduce


def compute(run: dict, trace: dict | None):
    return startup_reduce.metric(run, "setup_net_build_s")
