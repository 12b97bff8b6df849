"""A number the harness itself took on the host clock, by its name in
``run.facts`` (``first_query_s``: the first execution of the process)."""


def read(arg, run):
    return run.facts.get(arg)
