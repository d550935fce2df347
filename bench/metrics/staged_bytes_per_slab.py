"""Bytes the streaming engine's producer put to the device per slab: every
staged slab array (blocks, valid flags and, in a write-back
configuration, the write column), as ``streaming_stats()["pipeline"]
["staged_bytes"]`` sums them, over the window's jobs, over their slabs.
A program that keeps no such counter reads nothing."""


def read(run):
    staged = [j.stats.get("pipeline", {}).get("staged_bytes")
              for j in run.jobs]
    slabs = sum(j.n_slabs for j in run.jobs)
    if not staged or None in staged or not slabs:
        return None
    return sum(staged) / slabs
