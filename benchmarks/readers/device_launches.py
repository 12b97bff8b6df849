"""Executables launched on the device inside the traced window: the events
of the trace's module line, summed over the chips."""


def read(arg, run):
    return None if run.trace is None else run.trace["launches"]
