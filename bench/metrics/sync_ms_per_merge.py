"""Milliseconds per merge the host sat blocked on the device: the union of
the program's ``bpt.sync.*`` span intervals that start in the traced
window, over the window's merges.  None when no ``bpt.*`` span starts
there (a program without spans)."""
from devtrace import _union


def read(ctx):
    tr = ctx["trace"]
    spans = [e for e in tr.events if e.name.startswith("bpt.")
             and tr.start <= e.start < tr.end]
    if not spans:
        return None
    syncs = _union((e.start, e.start + e.dur) for e in spans
                   if e.name.startswith("bpt.sync."))
    return syncs / 1e6 / ctx["window_events"]
