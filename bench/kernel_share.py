"""Shared arithmetic of the ``<kernel>_roofline`` readers."""
from devtrace import custom_call_shapes


def kernel_ns(ctx, kind: str) -> float:
    """Device time of the family's ``kind`` Pallas calls outside the
    evaluation program, summed over the cell's chips."""
    tr, fam, cfg = ctx["trace"], ctx["family"], ctx["cfg"]
    total = 0.0
    for chip in tr.chips():
        in_eval = tr.in_module(chip, ctx["eval_program"])
        for e in tr.leaf_ops(chip):
            shapes = custom_call_shapes(e.name)
            if shapes and not in_eval(e) \
                    and fam.kernel_kind(shapes[0], shapes[1], cfg) == kind:
                total += e.dur
    return total


def roofline_share(ctx, kind: str, work) -> float | None:
    ns = kernel_ns(ctx, kind)
    if not ns or not ctx["window_images"]:
        return None
    flops, byt = work(ctx["cfg"], ctx["traffic"]["batch"])
    peak = ctx["peak"]
    least = ctx["window_images"] * max(flops / peak["bf16_flops_per_s"],
                                       byt / peak["hbm_bytes_per_s"])
    return 100.0 * least / (ns / 1e9)
