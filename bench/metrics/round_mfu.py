"""round_mfu: the DP-PASGD round program's model FLOPs over the chips'
bf16 peak, in the traced window. Model FLOPs come from shapes
(``flops.round_flops``: 6 per weight and token plus the mixer's own
contractions; recomputation does not count)."""


def read(ctx):
    if not ctx.get("window_s") or not ctx["rounds"]:
        return None
    work = ctx["rounds"] * ctx["round_flops"]
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * work / (ctx["window_s"] * peak)
