"""The one traffic generator: every mix is a data file under ``traffic/``
that this module reads.  The same seed gives the same inputs.

Training mixes (``"kind": "train"``) give batches of token rows.  Each row is
a document drawn as ``repro.data.tokens.TokenStream`` draws one (copied here,
so a change to the program's data pipeline does not move the yardstick):
Zipf-distributed tokens with a bigram structure.

Serving mixes (``"kind": "serve"``) give requests: a batch of prompts of one
length and a number of tokens to generate.  The mix lists the (prompt,
output) length pairs, drawn once and kept as data, so every seed offers the
same work; the seed orders them and draws the prompt tokens.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


# ------------------------------------------------------------------ train --

def sample_doc(rng: np.random.Generator, vocab: int, length: int, *,
               zipf_a: float, follow_p: float) -> np.ndarray:
    """One document of ``length`` tokens (TokenStream._sample_doc)."""
    base = rng.zipf(zipf_a, size=length).astype(np.int64) % vocab
    follow = (base * 2654435761 + 12345) % vocab
    coin = rng.random(length) < follow_p
    return np.where(coin, np.roll(follow, 1), base).astype(np.int32)


def train_batch(mix: dict, vocab: int, seed: int, index: int) -> dict:
    """Batch ``index`` of the mix: {"tokens", "labels"} of (global_batch,
    seq_len) int32, labels the tokens shifted by one.  Every row of every
    batch is its own document."""
    b, s = mix["global_batch"], mix["seq_len"]
    rows = np.empty((b, s + 1), np.int32)
    for i in range(b):
        rows[i] = sample_doc(_rng(seed, 1, index * b + i), vocab, s + 1,
                             zipf_a=mix["zipf_a"], follow_p=mix["follow_p"])
    return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}


# ------------------------------------------------------------------ serve --

@dataclass(frozen=True)
class Request:
    prompts: np.ndarray  # (batch, prompt_len) int32
    new_tokens: int

    @property
    def steps(self) -> int:
        return self.prompts.shape[1] + self.new_tokens


def serve_requests(mix: dict, vocab: int, seed: int, pass_index: int) -> list[Request]:
    """Pass ``pass_index`` over the mix's requests: its (prompt_len,
    new_tokens) pairs in an order drawn from the seed, each with its own
    prompt tokens."""
    pairs = mix["requests"]
    order = _rng(seed, 2, pass_index).permutation(len(pairs))
    out = []
    for k, i in enumerate(order):
        plen, new = pairs[int(i)]
        prompts = _rng(seed, 3, pass_index * len(pairs) + k).integers(
            0, vocab, (mix["batch"], plen), dtype=np.int64).astype(np.int32)
        out.append(Request(prompts, new))
    return out
