import pandas as pd

from match import results_match


def frame(**kw):
    return pd.DataFrame(kw)


def test_order_of_rows_does_not_matter():
    a = frame(k=["b", "a"], n=[2, 1], v=[2.0, 1.0])
    b = frame(k=["a", "b"], n=[1, 2], v=[1.0, 2.0])
    assert results_match(a, b)


def test_floats_within_tolerance_and_no_further():
    want = frame(v=[1.0e9])
    assert results_match(frame(v=[1.0e9 * (1 + 5e-7)]), want)
    assert not results_match(frame(v=[1.0e9 * (1 + 5e-6)]), want)


def test_integers_strings_and_dates_compare_exactly():
    want = frame(n=[100], s=["x"], d=pd.to_datetime(["1995-03-15"]))
    assert results_match(
        frame(n=[100], s=["x"],
              d=pd.to_datetime(["1995-03-15"]).astype("datetime64[ms]")),
        want)
    assert not results_match(
        frame(n=[101], s=["x"], d=pd.to_datetime(["1995-03-15"])), want)
    assert not results_match(
        frame(n=[100], s=["y"], d=pd.to_datetime(["1995-03-15"])), want)
    assert not results_match(
        frame(n=[100], s=["x"], d=pd.to_datetime(["1995-03-16"])), want)


def test_shape_and_names_must_agree():
    want = frame(a=[1, 2])
    assert not results_match(frame(a=[1]), want)
    assert not results_match(frame(b=[1, 2]), want)
    assert results_match(frame(a=[]), frame(a=[]))


def test_nulls_must_sit_in_the_same_places():
    assert results_match(frame(v=[1.0, None]), frame(v=[None, 1.0]))
    assert not results_match(frame(v=[1.0, None]), frame(v=[1.0, 2.0]))
