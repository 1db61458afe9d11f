"""Device time of the ops in the program's `attn` scope (forward, recompute
and backward) per traced train step, in ms, first chip."""
from chipbench.program_trace import train_scope_ms


def read(rec):
    return train_scope_ms(rec, "attn")
