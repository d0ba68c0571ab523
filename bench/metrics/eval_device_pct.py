"""Share of the traced window in which the device ran the Eq. 7/10 Q
evaluation program (the benchmark's own ``bench_eval_q``, vmapped over the
nodes or on one node's weights), averaged over the cell's chips."""


def read(ctx):
    tr = ctx["trace"]
    chips = tr.chips()
    if not chips:
        return None
    shares = [tr.module_ns(c, ctx["eval_program"]) / tr.window_ns
              for c in chips]
    return 100.0 * sum(shares) / len(shares)
