"""Host milliseconds the streaming engine's producer spent on a slab:
its ``sweep.produce`` spans (admission, marshalling, the H2D staging),
summed by ``streaming_stats()["pipeline"]["produce_s"]`` over the
window's jobs, over their slabs. Its wait for a free staging buffer is
not in it. A program that keeps no such span reads nothing."""


def read(run):
    produce = [j.stats.get("pipeline", {}).get("produce_s")
               for j in run.jobs]
    slabs = sum(j.n_slabs for j in run.jobs)
    if not produce or None in produce or not slabs:
        return None
    return 1e3 * sum(produce) / slabs
