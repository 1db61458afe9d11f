"""Model FLOPs of the traced train steps over their wall seconds times the
chips times the device kind's peak bf16 FLOP/s, in percent.  The FLOPs per
sequence come from the configuration's family reference."""
from chipbench.peaks import peaks


def read(rec):
    t = rec.traced
    if rec.kind != "train" or not t.get("steps") or t.get("wall_s", 0) <= 0:
        return None
    flops = rec.family.train_flops_per_seq(rec.config, t["seq"]) * t["rows"] * t["steps"]
    return 100.0 * flops / (t["wall_s"] * rec.chips * peaks(rec.device_kind)["bf16_flops"])
