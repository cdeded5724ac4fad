"""Share of the window the loop spent blocked in ``next(data_iter)``: the
loop's own ``data_wait_sec`` summed over the window's log intervals."""


def read(run):
    waits = [r["data_wait_sec"] for r in run.records if "data_wait_sec" in r]
    if not waits:
        return None
    return 100.0 * sum(waits) / run.window_s
