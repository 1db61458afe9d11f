"""Record the fixture of ``chipbench/tests/test_program_trace.py`` on one TPU
chip: two steps of the tiny dense train step of ``chipbench/tests/sizes.py``
inside the train cell's spans, then a two-token ``Generator.generate``, all
inside a ``window`` span.  Writes, gzipped, ``program_spans.xplane.pb`` and
``program_spans.hlo.txt``, the train step's instructions that the trace ran,
with their metadata.

    python3 chipbench/testdata/record_program_spans.py [--out DIR]
"""
from __future__ import annotations

import argparse
import gzip
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", type=Path, default=HERE)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import numpy as np

    from chipbench import bench, generator, trace
    from chipbench import program_trace as pt
    from chipbench.cell import span, traced
    from chipbench.tests.sizes import tiny_config, tiny_mix
    from repro.models import build_model
    from repro.optim import make_optimizer
    from repro.serving import Generator

    devices = jax.devices()[:1]
    if devices[0].platform != "tpu":
        print("the fixture is recorded on a TPU", file=sys.stderr)
        return 2
    cell = bench.workload("smollm-360m.train-8x2k")
    rec = bench.Record(workload="program_spans", config=tiny_config(cell["config"]),
                       mix=tiny_mix(cell["traffic"]), device_kind=devices[0].device_kind,
                       chips=1)
    step, mesh = pt.train_step(rec, devices)
    param_sh, opt_sh, batch_sh = step.in_shardings
    model, opt = build_model(step.arch), make_optimizer(step.arch.train)
    batches = [generator.train_batch(rec.mix, step.arch.model.vocab_size, 0, i)
               for i in range(3)]
    prompt = np.tile(np.arange(1, 4, dtype=np.int32), (2, 1))

    with mesh:
        st = {"params": jax.jit(model.init, out_shardings=param_sh)(jax.random.key(0))}
        st["opt"] = jax.jit(opt.init, out_shardings=opt_sh)(st["params"])

        def one_step(b) -> float:
            with span("batch transfer"):
                b = jax.device_put(b, batch_sh)
            with span("step dispatch"):
                st["params"], st["opt"], m = step.fn(st["params"], st["opt"], b)
            with span("wait"):
                jax.block_until_ready((st["params"], st["opt"], m))
            with span("loss fetch"):
                return float(m["loss"])

        gen = Generator(step.arch, jax.jit(model.init)(jax.random.key(1)), max_seq=8)
        one_step(batches[0])
        gen.generate(prompt, max_new_tokens=2)
        with traced(rec.workload) as tdir:
            with span("window"):
                for b in batches[1:]:
                    one_step(b)
                gen.generate(prompt, max_new_tokens=2)
    text = pt.train_hlo(rec, devices)

    from jax.profiler import ProfileData

    xplane = trace.find_xplane(tdir)
    chip = next(p for p in ProfileData.from_file(str(xplane)).planes
                if p.name == "/device:TPU:0")
    ran = {trace.op_name(ev.name) for ev in trace._line(chip, "XLA Ops")}
    keep = [line for line in text.splitlines()
            if (m := pt._INSTR.match(line)) and m.group(1) in ran]
    hlo = "\n".join(keep) + "\n"
    args.out.mkdir(parents=True, exist_ok=True)
    (args.out / "program_spans.xplane.pb.gz").write_bytes(
        gzip.compress(xplane.read_bytes(), mtime=0))
    (args.out / "program_spans.hlo.txt.gz").write_bytes(gzip.compress(hlo.encode(), mtime=0))
    print(pt.reduce_program(xplane, pt.hlo_ops(hlo), pt.TRAIN_MODULE))
    return 0


if __name__ == "__main__":
    sys.exit(main())
