"""All-lanes mining passes a job ran, the mean over the window's jobs: the
streaming engine's per-lane ``mine_passes`` counters as its
``streaming_stats()["mining"]["fused_passes"]`` sums them (one a pass on
each device). A program that keeps no such counter reads nothing."""


def read(run):
    passes = [j.stats.get("mining", {}).get("fused_passes")
              for j in run.jobs]
    if not passes or None in passes:
        return None
    return sum(passes) / len(passes)
