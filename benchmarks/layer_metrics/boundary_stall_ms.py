"""Milliseconds the device had nothing queued at a log boundary: from the
end of the loop's ``block_until_ready`` there to the return of the next
dispatch (``boundary_stall_sec``, reported with the interval in which it
ends), averaged over the window's boundaries. The idle the loop's own sync
causes. The window's first record reports the stall of the boundary that
opened the window, which began before it (and holds the harness's own
``start_trace``): it is left out."""


def read(run):
    stalls = [r["boundary_stall_sec"] for r in run.records[1:]
              if "boundary_stall_sec" in r]
    if not stalls:
        return None
    return 1e3 * sum(stalls) / len(stalls)
