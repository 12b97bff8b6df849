"""One field (``arg``) of ``device.memory_stats()`` after the window, of the
fullest chip."""


def read(arg, run):
    stats = [d.memory_stats() or {} for d in run.devices]
    values = [s[arg] for s in stats if arg in s]
    return max(values) if values else None
