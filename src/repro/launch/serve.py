"""Serving launcher: batched generation against any zoo arch.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \
        --batch 4 --prompt-len 16 --new-tokens 32

Uses the same decode_step the dry-run's decode_32k/long_500k cells lower,
on the default device, with weights cast to float32.  ``build_generator``
and ``serve()`` are the same path as functions, for callers that drive it in
their own process (``chip_smoke.py``).
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass

import jax
import numpy as np

from repro.configs import get_arch, get_reduced
from repro.configs.base import ArchConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving import Generator, perplexity


def build_generator(arch: ArchConfig, *, max_seq: int) -> Generator:
    """Generator over float32 weights of ``arch`` drawn from key 0."""
    arch = arch.replace(model=arch.model.replace(dtype="float32"))
    params = build_model(arch).init(jax.random.key(0))
    return Generator(arch, params, max_seq=max_seq)


@dataclass
class ServeRun:
    """What ``serve`` returns: one entry per request."""
    tokens: list[np.ndarray]   # (batch, prompt_len + new_tokens) int32
    request_s: list[float]     # host clock, until the tokens are on the host


def serve(gen: Generator, *, requests: int, batch: int, prompt_len: int,
          new_tokens: int, temperature: float = 0.0,
          seed: int = 0) -> ServeRun:
    """Serve ``requests`` batches of random prompts drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    run = ServeRun([], [])
    for r in range(requests):
        prompts = rng.integers(0, gen.arch.model.vocab_size,
                               (batch, prompt_len)).astype(np.int32)
        t0 = time.perf_counter()
        out = gen.generate(prompts, max_new_tokens=new_tokens,
                           temperature=temperature, seed=r)
        run.request_s.append(time.perf_counter() - t0)
        run.tokens.append(out)
    return run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--requests", type=int, default=2,
                    help="number of batched requests to serve")
    args = ap.parse_args()
    enable_compile_cache()

    arch = get_reduced(args.arch) if args.reduced else get_arch(args.arch)
    gen = build_generator(arch, max_seq=args.prompt_len + args.new_tokens + 1)
    run = serve(gen, requests=args.requests, batch=args.batch,
                prompt_len=args.prompt_len, new_tokens=args.new_tokens,
                temperature=args.temperature)
    for r, (out, dt) in enumerate(zip(run.tokens, run.request_s)):
        print(f"request {r}: {args.batch}x{args.new_tokens} tokens in "
              f"{dt:.2f}s  ppl={perplexity(gen.model, gen.params, out):.1f}")
    total_tok = args.requests * args.batch * args.new_tokens
    print(f"served {total_tok} tokens @ "
          f"{total_tok / sum(run.request_s):.1f} tok/s")


if __name__ == "__main__":
    main()
