"""Whole-step model FLOP utilization: training FLOPs per image (forward and
the required backward, from the configuration's shapes) times the images
trained per second in the traced window, over chips times the chip's
bf16 peak.  Evaluation FLOPs are not counted."""


def read(ctx):
    if not ctx["window_images"]:
        return None
    rate = ctx["window_images"] / ctx["window_s"]
    flops = ctx["family"].train_flops(ctx["cfg"]) * rate
    return 100.0 * flops / (ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
