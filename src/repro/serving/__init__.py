"""Batched serving: prefill + decode loop against the model zoo's cache API.

``Generator`` serves a batch of prompts: a prefill that writes the prompt
into the cache, then greedy or temperature sampling through ``decode_step``.
Dense models prefill through ``prefill_chunk``, ``PREFILL_CHUNK`` prompt
positions a call, which runs the same layer loop and cached attention
(``models.attention.gqa_decode``) as a decode step over a chunk of tokens
instead of one; every other family feeds the prompt through
``decode_step`` one position at a time.  The dry-run's decode shapes
(decode_32k, long_500k) lower the same ``Model.decode_step`` on the
production mesh through ``distributed.step.build_serve_step``; ``Generator``
jits it on one device.  Host spans in the profiler's trace name each
phase: ``prefill`` around the prompt, then per generated token ``sample``,
``token_fetch`` (the host waits for the token) and ``decode``.

Traffic-scale serving lives next door (DESIGN.md §14): open-loop arrival
processes in :mod:`repro.serving.arrivals`, the analytic per-step
:class:`~repro.serving.latency.LatencyModel`, and the request-driven
discrete-event simulator in :mod:`repro.serving.sim`.  ``Generator`` counts
its ``decode_step`` and ``prefill_chunk`` calls; where the prompt goes in
token by token, the parity suite pins the simulator's timing
byte-identically to this real path (``simulated_latency_s``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig
from repro.models import Model, build_model
from repro.serving.arrivals import (  # noqa: F401
    ARRIVALS, ArrivalProcess, list_arrivals, make_arrivals,
)
from repro.serving.latency import LatencyModel  # noqa: F401
from repro.serving.sim import (  # noqa: F401
    ServingResult, ServingSMLT, make_autoscaler, provision_for, serve,
)

PREFILL_CHUNK = 256   # prompt positions a call of the chunk program feeds


@dataclass
class Generator:
    arch: ArchConfig
    params: object
    max_seq: int = 512

    def __post_init__(self):
        self.model: Model = build_model(self.arch)
        assert self.model.cfg.supports_decode, "encoder models cannot decode"
        self.decode_fn = jax.jit(self.model.decode_step)
        self.prefill_fn = jax.jit(self.model.prefill_chunk)
        self.decode_steps = 0     # calls to decode_step (parity with sim)
        self.prefill_chunks = 0   # calls to prefill_chunk

    def _decode(self, *args):
        self.decode_steps += 1
        return self.decode_fn(*args)

    def simulated_latency_s(self, lat: LatencyModel) -> float:
        """Simulated seconds for the decode steps this Generator actually
        executed, under ``lat``'s per-step roofline -- the bridge the parity
        test pins against :func:`repro.serving.sim.serve`.  Only for a prompt
        fed token by token: the simulator does not model chunked prefill."""
        assert self.prefill_chunks == 0, "the simulator has no chunked prefill"
        return self.decode_steps * lat.step_s(1)

    def prefill(self, tokens: np.ndarray):
        """Write the prompt into a fresh cache.

        Dense models feed it in chunks of ``min(PREFILL_CHUNK, max_seq)``
        positions, the last one right-padded, through one compiled
        ``prefill_chunk`` for every prompt length; other families feed it
        through ``decode_step`` one position at a time.  Returns once the
        device has run it: (logits at the last prompt position, cache, next
        position)."""
        tokens = np.asarray(tokens, np.int32)
        b, s = tokens.shape
        assert s <= self.max_seq, (s, self.max_seq)
        with TraceAnnotation("prefill"):
            cache = self.model.init_cache(b, self.max_seq)
            logits = None
            if self.model.chunked_prefill:
                c = min(PREFILL_CHUNK, self.max_seq)
                for k in range(-(-s // c)):
                    # a last chunk that would run past the cache starts early:
                    # the write would be clamped onto the wrong positions, and
                    # positions fed twice come out the same
                    start = min(k * c, self.max_seq - c)
                    chunk = np.zeros((b, c), np.int32)
                    chunk[:, :min(c, s - start)] = tokens[:, start:start + c]
                    self.prefill_chunks += 1
                    logits, cache = self.prefill_fn(
                        self.params, cache, jnp.asarray(chunk),
                        jnp.int32(start), jnp.int32(min(s - 1 - start, c - 1)))
            else:
                for pos in range(s):
                    logits, cache = self._decode(self.params, cache,
                                                 jnp.asarray(tokens[:, pos]),
                                                 jnp.int32(pos))
            # the span holds the prefill's device time, not just its dispatch
            jax.block_until_ready(logits)
        return logits, cache, s

    def generate(self, prompts: np.ndarray, max_new_tokens: int = 32,
                 temperature: float = 0.0, seed: int = 0) -> np.ndarray:
        """prompts (b, s) int32 -> (b, s + max_new_tokens)."""
        prompts = np.asarray(prompts, np.int32)
        b, s = prompts.shape
        assert s + max_new_tokens <= self.max_seq
        logits, cache, pos = self.prefill(prompts)
        out = [prompts]
        key = jax.random.key(seed)
        tok = None
        for i in range(max_new_tokens):
            with TraceAnnotation("sample"):
                if temperature > 0:
                    key, sub = jax.random.split(key)
                    tok = jax.random.categorical(sub, logits / temperature, axis=-1)
                else:
                    tok = jnp.argmax(logits, axis=-1)
            with TraceAnnotation("token_fetch"):
                out.append(np.asarray(tok, np.int32)[:, None])
            with TraceAnnotation("decode"):
                logits, cache = self._decode(self.params, cache,
                                             tok.astype(jnp.int32),
                                             jnp.int32(pos + i))
        return np.concatenate(out, axis=1)


def perplexity(model: Model, params, tokens: np.ndarray) -> float:
    """Teacher-forced ppl via the training forward (consistency checks)."""
    batch = {"tokens": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}
    loss, _ = model.loss(params, batch)
    return float(jnp.exp(loss))
