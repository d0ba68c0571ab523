"""Milliseconds per merge of input work on the host: the union of the
program's ``bpt.draw`` (IDPA batch draw) and ``bpt.place`` (the batches'
placement on the device) span intervals that start in the traced window,
over the window's merges.  None when no ``bpt.*`` span starts there (a
program without spans)."""
from devtrace import _union


def read(ctx):
    tr = ctx["trace"]
    spans = [e for e in tr.events if e.name.startswith("bpt.")
             and tr.start <= e.start < tr.end]
    if not spans:
        return None
    inputs = _union((e.start, e.start + e.dur) for e in spans
                    if e.name in ("bpt.draw", "bpt.place"))
    return inputs / 1e6 / ctx["window_events"]
