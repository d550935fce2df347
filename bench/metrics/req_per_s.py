"""Simulated valid requests per second over the whole window: every
job's requests (padding lane-steps excluded) over the host seconds from
the first job's start to the last job's end."""


def read(run):
    return sum(j.requests for j in run.jobs) / run.window_s
