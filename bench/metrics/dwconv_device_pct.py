"""Share of the device's busy time outside the evaluation program (the
union of op intervals, summed over the cell's chips) spent in the
depthwise conv Pallas calls (``dwconv_fwd``, ``dwconv_dx``,
``dwconv_dw``) outside it: how much of the training step's device work
the depthwise conv is.  Nothing is read for a family without depthwise
calls (no ``DEPTHWISE_KIND``), nor from a trace that holds none."""
from devtrace import _union
from kernel_share import kernel_ns


def read(ctx):
    kind = getattr(ctx["family"], "DEPTHWISE_KIND", None)
    if kind is None:
        return None
    tr = ctx["trace"]
    busy = 0.0
    for chip in tr.chips():
        in_eval = tr.in_module(chip, ctx["eval_program"])
        busy += _union((e.start, e.start + e.dur) for e in tr.ops(chip)
                       if not in_eval(e))
    ns = kernel_ns(ctx, kind)
    if not ns or not busy:
        return None
    return 100.0 * ns / busy
