"""Generated tokens of every request in the window over the seconds from the
first request's start to the last one's completion."""


def read(rec):
    if rec.kind != "serve" or rec.window_s <= 0:
        return None
    return rec.work_tokens / rec.window_s
