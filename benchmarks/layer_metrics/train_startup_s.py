"""Seconds the program itself spent from the entry into ``train()`` to the
end of its first dispatch (state init, restore, probes, data set to the
device, the first chunk's compile or cache load and run): the loop's
``startup_sec``, the length of its ``train.startup`` span, a run constant
carried on every record. The part of ``setup_s`` that is the program's."""


def read(run):
    for r in run.records:
        if "startup_sec" in r:
            return float(r["startup_sec"])
    return None
