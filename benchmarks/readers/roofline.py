"""The least time the device could take for the traced executions, as a
share of the time it was busy, in percent. The least time is the bytes the
queries must read (``queries/<q>.py`` ``bytes_read``, each column once) over
the peak named by ``arg`` in ``peaks.json``: a scan-filter-aggregate query
does a few operations a byte, so memory bandwidth bounds it by construction,
and no count of operations is kept."""


def read(arg, run):
    if run.trace is None or not run.trace["busy_s"]:
        return None
    least_s = run.facts["bytes_read"] / run.peaks[arg]
    return 100.0 * least_s / run.trace["busy_s"]
