"""The growth of counters of the engine's registry over the window.
``arg`` is a name, or ``{"plus": [names], "minus": [names]}``; a name's
value is summed over its labels. A name the registry never saw reads 0 if
another of the names was seen, and the metric is left out if none was."""


def read(arg, run):
    if isinstance(arg, str):
        arg = {"plus": [arg]}

    def grown(name):
        seen = [k for k in run.counters_after if k[0] == name]
        if not seen:
            return None
        return sum(run.counters_after[k] - run.counters_before.get(k, 0)
                   for k in seen)
    parts = [(+1, grown(n)) for n in arg.get("plus", ())] \
        + [(-1, grown(n)) for n in arg.get("minus", ())]
    if all(v is None for _, v in parts):
        return None
    return sum(sign * (v or 0) for sign, v in parts)
