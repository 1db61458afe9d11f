"""Device time of the ops in the program's `head` and `loss` scopes (final
norm, unembedding, cross-entropy, and their backward) per traced train step,
in ms, first chip."""
from chipbench.program_trace import train_scope_ms


def read(rec):
    return train_scope_ms(rec, "head", "loss")
