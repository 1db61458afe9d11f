"""Serving cells: ``repro.serving.Generator.generate`` driven back to back by
one client, greedy, over the traffic mix's requests.

The window runs whole passes over the mix's requests (the same requests in
a seeded order), as many as fit in ``--seconds`` and at least one, and ends
when the last request completes, so no request is cut and every seed offers
the same work.  Once
the window has closed and the generator is freed, the plain reference judges
a sample of the finished requests, the longest among them."""
from __future__ import annotations

import jax
import numpy as np

from chipbench import generator
from chipbench.cell import Context, memory_peak, now, program_arch, span, traced
from chipbench.reference import serving as ref_serving
from chipbench.reference.common import weight_key
from chipbench.trace import find_xplane, reduce_trace

SPANS = ("request", "request boundary")
DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def run(ctx: Context) -> dict:
    from repro.models import build_model
    from repro.serving import Generator

    rec, mix, c = ctx.rec, ctx.rec.mix, ctx.rec.config
    fam = rec.family
    arch = program_arch(c, fam, mix, dtype=mix["dtype"])
    vocab = arch.model.vocab_size
    params = jax.jit(build_model(arch).init)(weight_key(ctx.seed))
    gen = Generator(arch, params, max_seq=mix["max_seq"])
    del params
    warm = np.zeros((mix["batch"], 1), np.int32)
    np.asarray(gen.generate(warm, max_new_tokens=1, temperature=0.0))

    done = []     # (request, served tokens (batch, prompt + new))

    def serve(req) -> None:
        with span("request"):
            out = gen.generate(req.prompts, max_new_tokens=req.new_tokens,
                               temperature=0.0)
        if ctx.fault == "altered_token":
            out = out.copy()
            out[0, -1] = (out[0, -1] + 1) % vocab
        done.append((req, out))

    setup_s = now() - ctx.t_start
    if ctx.trace:
        reqs = generator.serve_requests(mix, vocab, ctx.seed, 0)[: mix["trace_requests"]]
        with traced(rec.workload) as tdir:
            with span("window"):
                t0 = now()
                for req in reqs:
                    serve(req)
                wall = now() - t0
        rec.trace = reduce_trace(find_xplane(tdir), SPANS)
        rec.traced = {"requests": [(r.prompts.shape[0], r.prompts.shape[1], r.new_tokens)
                                   for r in reqs],
                      "wall_s": wall,
                      "bytes": {"weight_bytes": DTYPE_BYTES[mix["dtype"]],
                                "cache_bytes": DTYPE_BYTES[mix["dtype"]]}}
    else:
        t0, n = now(), 0
        while True:
            t_pass = now()
            with span("request boundary"):
                reqs = generator.serve_requests(mix, vocab, ctx.seed, n)
            for req in reqs:
                serve(req)
            n += 1
            if now() - t0 + (now() - t_pass) > ctx.seconds:
                break
        rec.window_s = now() - t0
        rec.work_tokens = sum(r.prompts.shape[0] * r.new_tokens for r, _ in done)
    rec.setup_s = setup_s
    peak = memory_peak(ctx.devices)
    ctx.log(f"decode_step calls {gen.decode_steps}; requests {len(done)}; "
            f"peak_bytes_in_use {peak}")
    del gen

    sample = [(req.prompts.shape[1], out)
              for req, out in _sample(done, ctx.seed, mix["check_requests"])]
    gap = max((ref_serving.widest_gap(c, fam, ctx.seed, out, plen, dtype=mix["dtype"],
                                      block_rows=mix["reference_rows"])
               for plen, out in sample), default=float("nan"))
    return {"numbers": {"served_logit_gap": gap}, "attempted": len(done),
            "failed": 0, "finite": True, "memory_peak_bytes": peak, "sample": sample}


def _sample(done: list, seed: int, n: int) -> list:
    """The longest finished request and n - 1 others drawn from the seed."""
    if not done:
        return []
    longest = max(range(len(done)), key=lambda k: done[k][0].steps)
    rest = [k for k in range(len(done)) if k != longest]
    rng = np.random.default_rng([int(seed), 4])
    pick = list(rng.permutation(rest)[: max(n - 1, 0)])
    return [done[k] for k in [longest] + pick]
