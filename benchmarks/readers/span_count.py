"""How many of the engine's host spans carry one name (``arg``, matched as
``span_sum`` does)."""

from span_sum import matches


def read(arg, run):
    if run.spans is None:
        return None
    return sum(1 for name, _ in run.spans if matches(name, arg))
