"""Tokens trained in the window over the window's seconds, all chips."""


def read(rec):
    if rec.kind != "train" or rec.window_s <= 0:
        return None
    return rec.work_tokens / rec.window_s
