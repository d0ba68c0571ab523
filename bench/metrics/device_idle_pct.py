"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (1 - union of op intervals / window)."""


def read(ctx):
    tr = ctx["trace"]
    chips = tr.chips()
    if not chips:
        return None
    idle = [1.0 - tr.busy_ns(c) / tr.window_ns for c in chips]
    return 100.0 * sum(idle) / len(idle)
