"""Share of the bucket rows executed that were padding, in percent:
1 - items served / bucket rows, over the calls dispatched in the window."""


def read(run):
    calls = run.window.calls_in_window()
    rows = sum(c.bucket for c in calls)
    return 100.0 * (1.0 - sum(c.rows for c in calls) / rows) if rows else None
