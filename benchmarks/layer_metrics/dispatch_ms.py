"""Host time spent enqueueing chunks, per step: the loop's own
``dispatch_sec`` summed over the window, over the window's steps."""


def read(run):
    spent = [r["dispatch_sec"] for r in run.records if "dispatch_sec" in r]
    if not spent or not run.steps:
        return None
    return 1e3 * sum(spent) / run.steps
