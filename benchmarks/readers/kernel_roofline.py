"""A kernel family's share of its roofline, in percent: the least seconds
the device could take to move the bytes the family's programs were handed,
over the seconds their operations ran on the device.

``arg``: ``{"bytes_counter": <registry counter>, "prefixes": [<module name
prefixes>], "peak": <a rate in peaks.json>}``. The engine counts the bytes
as it dispatches the family's programs, from what the host knows without a
sync: the rows of each input (its row count where that has been fetched,
else its capacity) at the least width a row of its schema occupies, each
input once. The output is not counted (its rows are known on the device
alone), so the share is a lower bound, and it reads the same whatever
implements the kernel; the device cannot move those bytes faster than the
peak, so it cannot read above 100."""

import counter_delta
import module_busy


def least_seconds(nbytes: float, bytes_per_s: float) -> float:
    return nbytes / bytes_per_s


def read(arg, run):
    nbytes = counter_delta.read(arg["bytes_counter"], run)
    busy_s = module_busy.read({"prefixes": arg["prefixes"]}, run)
    if not nbytes or not busy_s:
        return None
    return 100.0 * least_seconds(nbytes, run.peaks[arg["peak"]]) / busy_s
