"""Weights and optimizer state in set-up, host side: the ledger's `net/fill`
(the fillers, which dispatch asynchronously: what the device still owes
when the phase closes is in setup_unaccounted_s), `solver/opt state` and
`solver/place` phases. Layer: CLI_launch. Moves setup_s."""

import startup_reduce


def compute(run: dict, trace: dict | None):
    return startup_reduce.metric(run, "setup_fill_s")
