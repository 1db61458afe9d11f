"""Device idle inside the program's `prefill` spans per prompt position
fed, in ms, over the traced requests, first chip."""
from chipbench.program_trace import serve_span_ms


def read(rec):
    return serve_span_ms(rec, ("prefill",), idle=True, per="position")
