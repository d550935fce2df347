"""Device milliseconds per scan step in the ``amp`` scope: AMP's stream
detection, its prefetch inserts and degree feedback, by the operations'
own time inside the traced span."""

from bench.lib.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "amp")
