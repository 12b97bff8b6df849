"""Seconds in which an operation ran on the device: the union of the
device-operation intervals of the profiler trace, averaged over the chips."""


def read(arg, run):
    return None if run.trace is None else run.trace["busy_s"]
