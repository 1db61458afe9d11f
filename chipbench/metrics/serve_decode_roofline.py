"""Share of its roofline that the decode program reaches, in percent: the
sum over the decode calls of the traced requests of each call's least time
(the larger of its FLOPs over the peak and its HBM bytes over the peak
bandwidth), over the device time of those calls.  A call's bytes count the
key/value cache up to its position only, not the whole cache the program
holds."""
from chipbench.peaks import peaks

MODULE = "jit_decode_step"


def decode_calls(rec):
    """(batch, position) of every decode call of the traced requests, as the
    requests lay them out: one call per prompt token then one per generated
    token, or one per generated token where the prompt went in another
    program.  None where the trace counts neither."""
    n = rec.trace.module_calls.get(MODULE, 0)
    reqs = rec.traced.get("requests", [])
    every = [(b, pos) for b, plen, new in reqs for pos in range(plen + new)]
    if n == len(every):
        return every
    gen = [(b, pos) for b, plen, new in reqs for pos in range(plen, plen + new)]
    return gen if n == len(gen) else None


def least_times(rec):
    pk = peaks(rec.device_kind)
    calls = decode_calls(rec)
    if calls is None:
        return None, None
    out = {"flops": 0.0, "memory": 0.0}
    total = 0.0
    for b, pos in calls:
        f, nbytes = rec.family.decode_cost(rec.config, b, pos, **rec.traced["bytes"])
        tf, tb = f / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"]
        out["flops" if tf > tb else "memory"] += 1
        total += max(tf, tb)
    return total, out


def read(rec):
    if rec.kind != "serve" or rec.trace is None:
        return None
    device_s = rec.trace.module_s.get(MODULE, 0.0)
    total, _ = least_times(rec)
    if total is None or device_s <= 0:
        return None
    return 100.0 * total / device_s
