"""Seconds from the end of the program's start-up (its first dispatch
drained) to the boundary that opens the window: the warm-up chunks and
their log boundaries. The window's first record carries the process's age
at its own boundary (``process_age_sec``, the end of its ``train.interval``
span); less that interval's wall time (``loop_host_sec`` +
``data_wait_sec`` + ``device_sync_sec``) it is the age at the opening
boundary, and less ``before_train_sec`` and ``startup_sec`` what remains
is the warm-up. ``before_train_s`` + ``train_startup_s`` + this is
``setup_s`` as the program sees it."""

_KEYS = ("process_age_sec", "loop_host_sec", "data_wait_sec",
         "device_sync_sec", "before_train_sec", "startup_sec")


def read(run):
    if not run.records or any(k not in run.records[0] for k in _KEYS):
        return None
    r = run.records[0]
    opened = r["process_age_sec"] - (
        r["loop_host_sec"] + r["data_wait_sec"] + r["device_sync_sec"])
    return float(opened - r["before_train_sec"] - r["startup_sec"])
