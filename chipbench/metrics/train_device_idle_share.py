"""1 - device busy / traced window, in percent, over the train steps."""


def read(rec):
    if rec.kind != "train" or rec.trace is None or rec.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s / rec.trace.window_s)
