"""Share of executed lane-steps that carried no request: 1 - ideal
lane-steps / lane-steps, summed over the window's jobs (the streaming
engine's own deterministic counts)."""


def read(run):
    steps = sum(j.lane_steps for j in run.jobs)
    ideal = sum(j.ideal_lane_steps for j in run.jobs)
    return 100.0 * (1.0 - ideal / steps) if steps else None
