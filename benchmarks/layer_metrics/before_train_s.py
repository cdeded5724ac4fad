"""Seconds from the process's start to the entry into ``train()``: the
loop's ``before_train_sec``, the length of its ``process.before_train``
span (the start read from ``/proc``; the package's first import line
where there is none), a run constant carried on every record. What the
caller does before it (imports, the data set, the planted checkpoint) is
the part of ``setup_s`` that comes before the program's own start-up."""


def read(run):
    if not run.records or "before_train_sec" not in run.records[0]:
        return None
    return float(run.records[0]["before_train_sec"])
