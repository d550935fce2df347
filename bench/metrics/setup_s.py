"""Seconds from process start to the first timed job: imports, volume
generation, compile-cache load and the warm-up job."""


def read(run):
    return run.setup_s
