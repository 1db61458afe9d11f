"""Batched serving: prefill + decode loop against the model zoo's cache API.

``Generator`` serves a batch of prompts: a prefill that feeds the prompt
through ``decode_step`` one position at a time (every family), then greedy
or temperature sampling through ``decode_step``.  The same ``serve_step`` is
what the decode_32k / long_500k dry-run shapes lower, so everything here
runs identically under `jit` on the production mesh.  Host spans in the
profiler's trace name each phase: ``prefill`` around the prompt, then per
generated token ``sample``, ``token_fetch`` (the host waits for the token)
and ``decode``.

Traffic-scale serving lives next door (DESIGN.md §14): open-loop arrival
processes in :mod:`repro.serving.arrivals`, the analytic per-step
:class:`~repro.serving.latency.LatencyModel`, and the request-driven
discrete-event simulator in :mod:`repro.serving.sim`.  ``Generator`` counts
its ``decode_step`` calls so the parity suite can pin the simulator's
timing byte-identically to this real path (``simulated_latency_s``).
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


@dataclass
class Generator:
    arch: ArchConfig
    params: object
    max_seq: int = 512

    def __post_init__(self):
        self.model: Model = build_model(self.arch)
        assert self.model.cfg.supports_decode, "encoder models cannot decode"
        self.decode_fn = jax.jit(self.model.decode_step)
        self.decode_steps = 0     # calls to decode_step (parity with sim)

    def _decode(self, *args):
        self.decode_steps += 1
        return self.decode_fn(*args)

    def simulated_latency_s(self, lat: LatencyModel) -> float:
        """Simulated seconds for the decode steps this Generator actually
        executed, under ``lat``'s per-step roofline -- the bridge the parity
        test pins against :func:`repro.serving.sim.serve`."""
        return self.decode_steps * lat.step_s(1)

    def prefill(self, tokens: np.ndarray):
        """Generic prefill: feed prompt tokens through decode_step.

        Returns (logits at the last prompt position, cache, next position)."""
        b, s = tokens.shape
        with TraceAnnotation("prefill"):
            cache = self.model.init_cache(b, self.max_seq)
            logits = None
            for pos in range(s):
                logits, cache = self._decode(self.params, cache,
                                             jnp.asarray(tokens[:, pos]),
                                             jnp.int32(pos))
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
