"""Images the host data engine decoded per second of the window: the
engine's interval rate (``data_decode_images_per_sec``, counted between
two log boundaries) times each interval, over the window."""


def read(run):
    done = [r["data_decode_images_per_sec"] * r["_dt"] for r in run.records
            if "data_decode_images_per_sec" in r]
    if not done:
        return None
    return sum(done) / run.window_s
