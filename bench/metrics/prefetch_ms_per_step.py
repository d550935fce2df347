"""Device milliseconds per scan step in the ``prefetch`` scope:
MITHRIL's prefetch-table lookup and its prefetch inserts into the
cache, by the operations' own time inside the traced span."""

from bench.lib.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "prefetch")
