"""Seconds in which operations of some programs ran on the device: the
union of the ``XLA Ops`` intervals that lie inside ``XLA Modules`` events
whose name starts with one of ``arg["prefixes"]`` (or, with ``arg["not"]``,
with none of those: operations inside no module event at all count there
too), clipped to ``bench.window`` and averaged over the chips.

The engine names each program after its kernel family (``jit_srt_aggupd``,
``jit_srt_join``...; a program it has not named is ``jit__lambda_`` or an
eager operation's ``jit_convert_element_type``). Readers whose arguments
split the module names between them add up to ``device_busy``.

The reduction the harness hands every reader (``run.trace``) keeps no
module intervals, so this reader loads the trace again (``trace_reduce.load``),
once a run: what it loads is kept on ``run`` for the next metric of its kind.
``busy`` works on plain interval lists and is checked on hand-made ones.
"""

import os

import trace_reduce
from trace_reduce import clip, merge, total

TRACE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(trace_reduce.__file__)), ".trace")


def selects(name, arg) -> bool:
    """Whether the module ``name`` (None: no module) is one of ``arg``'s."""
    if "not" in arg:
        return name is None or not name.startswith(tuple(arg["not"]))
    return name is not None and name.startswith(tuple(arg["prefixes"]))


def busy(ops, modules, window, arg) -> float:
    """``ops`` and ``modules``: {device: [(name, start, end)]}; ``window``:
    (start, end). An operation belongs to the module event that holds its
    start: the device runs one program at a time."""
    lo, hi = window
    each = []
    for dev in sorted(ops):
        mods = sorted(modules.get(dev, ()), key=lambda m: m[1])
        mine, at = [], 0
        for _, a, b in sorted(ops[dev], key=lambda o: o[1]):
            while at + 1 < len(mods) and mods[at + 1][1] <= a:
                at += 1
            inside = mods and mods[at][1] <= a < mods[at][2]
            if selects(mods[at][0] if inside else None, arg):
                mine.append((a, b))
        each.append(total(merge(clip(mine, lo, hi))))
    return sum(each) / len(each) if each else 0.0


def read(arg, run):
    if run.trace is None:
        return None
    loaded = getattr(run, "module_intervals", None)
    if loaded is None:
        try:
            path = trace_reduce.find_xplane(TRACE_DIR)
        except FileNotFoundError:
            return None
        ops, modules, _, window, _ = trace_reduce.load(
            path, (), run.devices[0].platform)
        loaded = run.module_intervals = (ops, modules, window)
    ops, modules, window = loaded
    if window is None or not ops:
        return None
    return busy(ops, modules, window[:2], arg)
