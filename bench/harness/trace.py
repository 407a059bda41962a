"""Reduction of a profiler trace to device metrics.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Read with
``jax.profiler.ProfileData``:

* each TPU chip is a plane ``/device:TPU:<i>`` whose line ``XLA Ops`` holds
  one event per operation run, named by its HLO instruction's text (with
  the operand shapes); a ``while`` op's event spans its body's events;
* the host is the plane ``/host:CPU``; the harness's own
  ``jax.profiler.TraceAnnotation`` spans (``window`` around the measured
  window; ``generator_wait``, ``batch_form``, ``device_execute`` and
  ``fan_out`` inside it) are events there, on the same clock.

Busy time is the union of the operations' intervals inside the window,
averaged over the chips; idle gaps are the rest of the window, each
split by the host annotations open over them.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

from . import kernels

WINDOW = "window"
HOST_LABELS = ("generator_wait", "batch_form", "device_execute", "fan_out")
OP_LINE = "XLA Ops"
_LAYOUT = re.compile(r"\{[^{}]*\}")


def load(trace_dir: str):
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(files[-1])


def device_ops(pd) -> dict[str, list[tuple[str, int, int]]]:
    """{device: [(op name, start ns, end ns), ...]}."""
    out = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops += [(e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                            for e in line.events]
            out[plane.name] = ops
    return out


def host_spans(pd) -> dict[str, list[tuple[int, int]]]:
    """{annotation name: [(start ns, end ns), ...]} for the harness's
    annotations on the host plane."""
    want = (WINDOW,) + HOST_LABELS
    out = defaultdict(list)
    cpu = pd.find_plane_with_name("/host:CPU")
    for line in cpu.lines if cpu is not None else ():
        for e in line.events:
            if e.name in want:
                out[e.name].append((int(e.start_ns),
                                    int(e.start_ns + e.duration_ns)))
    return dict(out)


def merge(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Union of ``intervals`` clipped to [lo, hi], sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: list[tuple[int, int]], lo: int, hi: int):
    t = lo
    for s, e in busy:
        if s > t:
            yield t, s
        t = max(t, e)
    if hi > t:
        yield t, hi


class Labels:
    """What the host was doing: the harness annotations, which follow one
    another and do not nest; time outside them is ``other``."""

    def __init__(self, spans: dict):
        self.iv = sorted((s, e, name) for name in HOST_LABELS
                         for s, e in spans.get(name, ()))
        self.starts = [s for s, _, _ in self.iv]

    def split(self, lo: int, hi: int):
        """[(label, ns)] covering [lo, hi)."""
        out, t = [], lo
        i = max(bisect.bisect_right(self.starts, lo) - 1, 0)
        while t < hi and i < len(self.iv):
            s, e, name = self.iv[i]
            i += 1
            if e <= t:
                continue
            if s >= hi:
                break
            if s > t:
                out.append(("other", s - t))
            out.append((name, min(e, hi) - max(s, t)))
            t = min(e, hi)
        if t < hi:
            out.append(("other", hi - t))
        return out


def instruction(text: str) -> str:
    """An op event's name is its HLO instruction's text; the instruction
    name is what stands before `` = ``."""
    return text.split(" = ", 1)[0].lstrip("%")


def short(text: str, width: int = 120) -> str:
    """The instruction's text without layouts, cut to ``width``."""
    prev = None
    while prev != text:
        prev, text = text, _LAYOUT.sub("", text)
    return text.lstrip("%")[:width]


def self_times(ops) -> dict[str, float]:
    """Seconds of each op less the ops nested in it (a ``while`` holds
    its body's ops on the same line), summed by op text."""
    ns = defaultdict(int)
    stack = []                      # [text, end, child ns]
    for text, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            t, _, child = stack.pop()
            ns[t] -= child
        if stack:
            stack[-1][2] += min(e, stack[-1][1]) - s
        ns[text] += e - s
        stack.append([text, e, 0])
    for t, _, child in stack:
        ns[t] -= child
    return {t: v / 1e9 for t, v in ns.items()}


def reduce(pd, top: int = 10) -> dict:
    """Busy and window seconds, device self time per operation, each
    kernel's time and work (from the shapes in its events' text,
    ``kernels.WORK``), and idle time by host label."""
    spans = host_spans(pd)
    if not spans.get(WINDOW):
        raise ValueError("the trace holds no 'window' annotation")
    lo, hi = spans[WINDOW][0]
    devs = device_ops(pd)
    if not devs:
        raise ValueError("the trace holds no device operation")
    busy_s, op_s = [], defaultdict(float)
    kern = defaultdict(lambda: {"seconds": 0.0, "bytes": 0.0, "flops": 0.0,
                                "calls": 0})
    idle = defaultdict(float)
    n_dev = len(devs)
    for i, (_, ops) in enumerate(sorted(devs.items())):
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if e > lo and s < hi]
        busy = merge([(s, e) for _, s, e in inside], lo, hi)
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        for text, sec in self_times(inside).items():
            op_s[short(text)] += sec / n_dev
        for text, s, e in inside:
            name = instruction(text)
            for kname, work in kernels.WORK.items():
                if name.startswith(kname):
                    w = work(kernels.custom_calls(text, kname)[name])
                    k = kern[kname]
                    k["seconds"] += (e - s) / 1e9 / n_dev
                    k["bytes"] += w["bytes"] / n_dev
                    k["flops"] += w["flops"] / n_dev
                    k["calls"] += 1
        if i == 0:
            labels = Labels(spans)
            for s, e in gaps(busy, lo, hi):
                for name, ns in labels.split(s, e):
                    idle[name] += ns / 1e9
    ops_top = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    idle_top = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy_s) / n_dev,
            "devices": n_dev,
            "device_ops": [[n, s] for n, s in ops_top],
            "idle_gaps": [[n, s] for n, s in idle_top],
            "kernels": {k: dict(v) for k, v in kern.items()}}
