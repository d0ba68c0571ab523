"""From a profiler trace to the events that the metric readers use.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes and
keeps, as plain ``Event`` tuples on the trace's one clock (nanoseconds):

- on each TPU plane ``/device:TPU:<n>``, the lines ``XLA Ops`` (one event
  per HLO operation run) and ``XLA Modules`` (one per program run);
- on the host, the benchmark's own spans (``bench.*`` annotations, on
  whichever thread line they land) and the events of the main Python
  thread, to say what the host was doing while the device idled.

``Trace`` reduces them: the window is the ``bench.window`` span; device
operations are clipped to it; busy time is the union of the operation
intervals (a ``while`` operation spans its body, so the union counts each
instant once); a Pallas call is a ``tpu_custom_call`` operation, whose
operand shapes the family sorts into kernels.
"""
from __future__ import annotations

import collections
import re

Event = collections.namedtuple("Event", "plane line name start dur")

DEVICE_PREFIX = "/device:TPU:"
OPS, MODULES = "XLA Ops", "XLA Modules"
_CONTROL_FLOW = ("while", "conditional", "call")
_OPERANDS = re.compile(r"(\w+\[[\d,]*\])\{[^}]*\} %")


def load(path: str) -> list:
    """The events of one ``.xplane.pb`` that the reduction needs."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        name = plane.name
        for line in plane.lines:
            if name.startswith(DEVICE_PREFIX):
                if line.name not in (OPS, MODULES):
                    continue
                keep = None
            elif name == "/host:CPU":
                keep = line.name == "python" or line.name.startswith("main")
            else:
                continue
            for e in line.events:
                if keep is False and not e.name.startswith("bench."):
                    continue
                out.append(Event(name, line.name, e.name, float(e.start_ns),
                                 float(e.duration_ns)))
    return out


def _union(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def op_name(name: str) -> str:
    """``%fusion.12 = ...`` -> ``fusion``; the HLO instruction's stem."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def custom_call_shapes(name: str):
    """(result shape, [operand shapes]) of a Pallas call, else None."""
    if 'custom_call_target="tpu_custom_call"' not in name:
        return None
    _, rest = name.split(" = ", 1)
    out = re.sub(r"\{.*", "", rest.split(" custom-call(", 1)[0])
    args = rest.split("custom-call(", 1)[1].split("), custom_call_target")[0]
    return out, _OPERANDS.findall(args)


class Trace:
    def __init__(self, events: list):
        self.events = events
        spans = [e for e in events if e.name == "bench.window"]
        if not spans:
            raise ValueError("trace holds no bench.window span")
        w = spans[0]
        self.start, self.end = w.start, w.start + w.dur

    @property
    def window_ns(self) -> float:
        return self.end - self.start

    def chips(self) -> list:
        return sorted({e.plane for e in self.events
                       if e.plane.startswith(DEVICE_PREFIX)},
                      key=lambda p: int(p[len(DEVICE_PREFIX):]))

    def _clipped(self, plane: str, line: str) -> list:
        out = []
        for e in self.events:
            if e.plane != plane or e.line != line:
                continue
            s, t = max(e.start, self.start), min(e.start + e.dur, self.end)
            if t > s:
                out.append(Event(e.plane, e.line, e.name, s, t - s))
        return out

    def ops(self, chip: str) -> list:
        return self._clipped(chip, OPS)

    def modules(self, chip: str) -> list:
        return self._clipped(chip, MODULES)

    def busy_ns(self, chip: str) -> float:
        return _union((e.start, e.start + e.dur) for e in self.ops(chip))

    def leaf_ops(self, chip: str) -> list:
        """Operations that do not contain others (no control flow)."""
        return [e for e in self.ops(chip)
                if op_name(e.name) not in _CONTROL_FLOW]

    def in_module(self, chip: str, prefix: str):
        """Predicate: does an op of ``chip`` lie in a run of a program
        whose name starts with ``prefix``?"""
        runs = [(m.start, m.start + m.dur) for m in self.modules(chip)
                if m.name.startswith(prefix)]

        def inside(e) -> bool:
            mid = e.start + e.dur / 2
            return any(s <= mid <= t for s, t in runs)
        return inside

    def module_ns(self, chip: str, prefix: str) -> float:
        return _union((m.start, m.start + m.dur) for m in self.modules(chip)
                      if m.name.startswith(prefix))

    def idle_gaps(self, chip: str, top: int = 10) -> list:
        """The longest idle gaps of ``chip`` in the window, each named by
        the innermost host event that covers its middle."""
        busy, end = [], self.start
        for s, e in sorted((o.start, o.start + o.dur)
                           for o in self.ops(chip)):
            if s > end:
                busy.append((end, s))
            end = max(end, e)
        if self.end > end:
            busy.append((end, self.end))
        host = [e for e in self.events if not e.plane.startswith(
            DEVICE_PREFIX) and e.name != "bench.window"]
        out = []
        for s, e in sorted(busy, key=lambda g: g[0] - g[1])[:top]:
            mid = (s + e) / 2
            cover = [h for h in host if h.start <= mid <= h.start + h.dur]
            label = min(cover, key=lambda h: h.dur).name if cover else "none"
            out.append([label, (e - s) / 1e9])
        return out

    def top_ops(self, chip: str, top: int = 10) -> list:
        """The device operations that took most time, summed by stem."""
        acc = collections.Counter()
        for e in self.leaf_ops(chip):
            shapes = custom_call_shapes(e.name)
            key = f"pallas {shapes[0]}" if shapes else op_name(e.name)
            acc[key] += e.dur
        return [[k, v / 1e9] for k, v in acc.most_common(top)]
