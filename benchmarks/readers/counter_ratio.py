"""The growth of one set of the engine's counters over the growth of
another, over the window: ``arg`` is ``{"num": <a counter_delta arg>,
"den": <a counter_delta arg>}``, each side read as ``counter_delta`` reads
it. Nothing where either side has nothing to read, or the denominator did
not grow. Timers count seconds, so seconds of work over seconds of wall is
a mean number in flight (Little's law)."""

import counter_delta


def read(arg, run):
    num = counter_delta.read(arg["num"], run)
    den = counter_delta.read(arg["den"], run)
    if num is None or not den:
        return None
    return num / den
