"""Seconds inside the engine's host spans of one name (``arg``; a name that
ends in ``.`` or ``*`` takes every span that starts with it), summed over
every thread, so a pool's spans may exceed the wall."""


def matches(name: str, arg: str) -> bool:
    if arg.endswith("*"):
        arg = arg[:-1]
    return name.startswith(arg) if arg.endswith(".") else name == arg


def read(arg, run):
    if run.spans is None:
        return None
    return sum(dur for name, dur in run.spans if matches(name, arg))
