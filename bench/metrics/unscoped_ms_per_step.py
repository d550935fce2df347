"""Device milliseconds per scan step of the operations in none of the
chunk runner's named scopes (``bench/lib/scopes.py``): the loop's own
operations, the asynchronous copies XLA gives no op_name, and work
outside the runner (lane resets, a job's initial carry). With the
scoped metrics and the ``record`` scope it adds up to the busy time."""

from bench.lib.scopes import UNSCOPED, ms_per_step


def read(run):
    return ms_per_step(run, UNSCOPED)
