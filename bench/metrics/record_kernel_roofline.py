"""The record kernel's share of its roofline: the least time the chip
needs for the recording events of the traced span over the kernel's own
device time there. A recording event is a demand miss
(``bench/lib/roofline.record_event``); the span holds the kernel's
launches, one per scan step, at the window's misses per step."""

from bench.lib import roofline
from bench.lib.kernels import kernel_launches, kernel_seconds


def read(run):
    t = run.trace
    if t is None:
        return None
    seconds = kernel_seconds(t, "record")
    misses = sum(int((j.counts[:, 0] - j.counts[:, 1]).sum())
                 for j in run.jobs)
    steps = sum(j.n_slabs for j in run.jobs) * run.cell.shape["chunk"]
    events = misses / steps * kernel_launches(t, "record")
    work = roofline.record_event(run.cell.config).scaled(events)
    return 100.0 * roofline.least_time(
        work, roofline.peaks(run.device_kind)) / seconds
