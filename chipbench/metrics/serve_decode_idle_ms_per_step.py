"""Device idle inside the program's per-token `sample`, `token_fetch` and
`decode` spans per generated step, in ms, over the traced requests, first
chip."""
from chipbench.program_trace import DECODE_SPANS, serve_span_ms


def read(rec):
    return serve_span_ms(rec, DECODE_SPANS, idle=True, per="step")
