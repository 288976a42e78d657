"""Host seconds of tuning the cell's plan at set-up (``extract_workload``
and ``core.tune``); nothing in a cell with no plan."""


def read(ctx):
    return ctx["rec"].get("tune_s")
