"""Host syncs recorded over the window: the growth of ``SYNC_LEDGER.seq``,
an exact count of the points at which the host blocked on the device."""


def read(arg, run):
    if run.ledger_before is None:
        return None
    return run.ledger_after - run.ledger_before
