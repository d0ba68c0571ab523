"""Share of each chip's idle time in the traced window (the complement of
the union of its operation intervals, as ``device_idle_pct`` has it) that
no program span names: covered by no ``bpt.*`` span other than
``bpt.round``, so ``bpt.round``'s self time counts as unattributed, as
does whatever lies outside it.  Spans count when they start in the
window.  Mean over chips; None when no ``bpt.*`` span starts in the
window (a program without spans)."""


def _merged(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a: list, b: list) -> float:
    """Length of the intersection of two merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(ctx):
    tr = ctx["trace"]
    spans = [e for e in tr.events if e.name.startswith("bpt.")
             and tr.start <= e.start < tr.end]
    chips = tr.chips()
    if not spans or not chips:
        return None
    named = _merged((e.start, min(e.start + e.dur, tr.end)) for e in spans
                    if e.name != "bpt.round")
    shares = []
    for chip in chips:
        busy = _merged((o.start, o.start + o.dur) for o in tr.ops(chip))
        idle, end = [], tr.start
        for s, e in busy:
            if s > end:
                idle.append([end, s])
            end = max(end, e)
        if tr.end > end:
            idle.append([end, tr.end])
        idle_ns = sum(e - s for s, e in idle)
        if idle_ns > 0:
            shares.append(1.0 - _overlap(idle, named) / idle_ns)
        else:
            shares.append(0.0)
    return 100.0 * sum(shares) / len(shares)
