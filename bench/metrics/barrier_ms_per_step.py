"""Device milliseconds per scan step in the mining barrier's scope: the
batch-level ``lax.cond`` around ``mine_batched`` (both of its paths and
their kernels) and the layout copies XLA hands its output on with, by
the operations' own time inside the traced span."""

from bench.lib.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "barrier")
