"""Device busy time per step: the union of the operations' intervals on a
device (averaged over the devices) over the steps of the capture."""


def read(run):
    if run.trace is None or not run.steps:
        return None
    return 1e3 * run.trace["busy_s"] / run.steps
