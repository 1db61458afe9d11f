"""Device time of the ops in the program's `optimizer` scope (gradient clip
and AdamW) per traced train step, in ms, first chip."""
from chipbench.program_trace import train_scope_ms


def read(rec):
    return train_scope_ms(rec, "optimizer")
