"""The comparison that decides ``correct``: an engine answer against the
plain reference's, as two pandas frames.

A copy of ``bench._results_match`` kept with the yardstick, without its
branch for explicitly rounded outputs (no query here rounds): rows are
compared without regard to order; keys, counts, integers, strings and dates
exactly; float columns within ``rtol 1e-6, atol 1e-9``, because the engine
sums in another order than pandas and in blocks. A computation in lower
precision than float64 fails it (float32 sums of 1e5 rows differ near 1e-4).
"""

import numpy as np

RTOL = 1e-6
ATOL = 1e-9


def _is_float(series) -> bool:
    dtype = series.dtype
    return dtype.kind == "f" or (hasattr(dtype, "numpy_dtype")
                                 and dtype.numpy_dtype.kind == "f")


def _canonical(df):
    """Rows in one order: lexsort by every column, first column first.
    Floats are rounded for the sort only, so that last-digit differences
    between two answers cannot reorder rows whose other columns tie."""
    keys = []
    for i in range(df.shape[1] - 1, -1, -1):
        col = df.iloc[:, i]
        if _is_float(col):
            keys.append(np.round(col.to_numpy(dtype=float), 6))
        else:
            keys.append(col.astype(str).to_numpy())
    return df.iloc[np.lexsort(keys)].reset_index(drop=True)


def results_match(got, want) -> bool:
    if len(got) != len(want) or list(got.columns) != list(want.columns):
        return False
    if len(got) == 0:
        return True
    g, w = _canonical(got), _canonical(want)
    for i in range(g.shape[1]):
        gv, wv = g.iloc[:, i], w.iloc[:, i]
        gnull = gv.isna().to_numpy()
        if not (gnull == wv.isna().to_numpy()).all():
            return False
        both = ~gnull
        if _is_float(gv) or _is_float(wv):
            if not np.isclose(gv.to_numpy(dtype=float)[both],
                              wv.to_numpy(dtype=float)[both],
                              rtol=RTOL, atol=ATOL, equal_nan=True).all():
                return False
        elif not (gv[both].astype(str).to_numpy()
                  == wv[both].astype(str).to_numpy()).all():
            return False
    return True
