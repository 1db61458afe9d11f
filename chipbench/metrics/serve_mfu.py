"""Model FLOPs of the traced requests' decode calls over the traced
window's wall seconds times the chips times the peak bf16 FLOP/s, in
percent: the whole serving step's share of the chip's peak."""
from chipbench.metrics.serve_decode_roofline import decode_calls
from chipbench.peaks import peaks


def read(rec):
    if rec.kind != "serve" or rec.trace is None or rec.traced.get("wall_s", 0) <= 0:
        return None
    calls = decode_calls(rec)
    if calls is None:
        return None
    flops = sum(rec.family.decode_cost(rec.config, b, pos, **rec.traced["bytes"])[0]
                for b, pos in calls)
    return 100.0 * flops / (rec.traced["wall_s"] * rec.chips
                            * peaks(rec.device_kind)["bf16_flops"])
