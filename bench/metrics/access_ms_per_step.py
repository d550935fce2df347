"""Device milliseconds per scan step in the ``access`` scope: the demand
access to the cache (probe, LRU stamp, demand insert and eviction) and
its counters, by the operations' own time inside the traced span."""

from bench.lib.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "access")
