"""The share of the traced window in which no operation ran on the device,
in percent."""


def read(arg, run):
    if run.trace is None or run.trace["idle_share"] is None:
        return None
    return 100.0 * run.trace["idle_share"]
