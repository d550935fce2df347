"""Device milliseconds per scan step of every operation that is not one of
the MITHRIL kernels: the chunk runner's XLA work (LRU, AMP, the
prefetch-table update and relayout, the mining branch's inserts), by the
operations' own time inside the traced span. Every scan step launches
the record kernel once, so its launches in the span count the steps."""

from bench.lib.kernels import all_kernel_ops, kernel_launches


def read(run):
    t = run.trace
    if t is None:
        return None
    steps = kernel_launches(t, "record")
    names = all_kernel_ops(t)
    other = sum(s for op, s in t.op_seconds().items() if op not in names)
    return 1e3 * other / steps
