"""Share of its intervals the loop thread itself held: each interval's wall
time less the time blocked in ``next(data_iter)`` and the time blocked on
the device at the boundary (the loop's ``loop_host_sec``, on its own
clock), over the wall time of the same intervals. The loop cuts an interval
where the boundary's sync ends, before it calls the writer, so the window's
first record covers an interval that began before the window (and holds
the harness's own ``start_trace``): it is left out."""


def read(run):
    inside = [r for r in run.records[1:] if "loop_host_sec" in r]
    wall = sum(r["loop_host_sec"] + r.get("data_wait_sec", 0.0)
               + r.get("device_sync_sec", 0.0) for r in inside)
    if not wall:
        return None
    return 100.0 * sum(r["loop_host_sec"] for r in inside) / wall
