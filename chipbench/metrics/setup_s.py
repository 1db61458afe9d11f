"""Set-up: process start to the first timed step or request (host clock)."""


def read(rec):
    return rec.setup_s
