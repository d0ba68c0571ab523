"""Host syncs per merge: the program's ``bpt.sync.*`` spans (each one
sanctioned device-to-host pull, ``repro.sanitize.sanctioned_scope``) that
start in the traced window, over the window's merges.  None when no
``bpt.*`` span starts there (a program without spans)."""


def read(ctx):
    tr = ctx["trace"]
    spans = [e for e in tr.events if e.name.startswith("bpt.")
             and tr.start <= e.start < tr.end]
    if not spans:
        return None
    syncs = sum(e.name.startswith("bpt.sync.") for e in spans)
    return syncs / ctx["window_events"]
