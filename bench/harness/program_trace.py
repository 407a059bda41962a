"""What the program marks in a profile, reduced to per-layer numbers.

Two marks, each read beside the device trace that ``trace.py`` reduces:

* **Named scopes.**  The beam engines run each phase of their loop bodies
  under a ``jax.named_scope`` (``hop.select``, ``hop.expand``,
  ``hop.visited``, ``hop.distance``, ``hop.merge``, ``hop.transition``;
  the probing engine adds ``hop.estimate``).  The scope reaches the
  compiled program as the ``op_name`` in each instruction's ``metadata``,
  whatever XLA names the instruction.  ``scope_map`` reads it from the
  compiled program's HLO text, so that a device op event (named by its
  instruction) can be charged to its phase.  An instruction the compiler
  made without metadata of its own (a fusion takes its root's; a copy or a
  broadcast of a constant has none) takes the phase of the op that
  consumes it, else of the op that feeds it.  The profile's own ``tf_op``
  stat is no substitute: ``ProfileData`` does not expose it, and it is
  missing from exactly those instructions (on the recorded TPU fixture it
  covers 88.3% of busy time, the map 93.3%).
* **Mirrored spans.**  A ``repro.obs.Tracer`` writes each of its spans
  that is not retroactive as a ``TraceAnnotation`` of the same name on the
  host plane; the serve loop's are named ``serve.*``.  ``reduce`` splits the window's
  idle device time by the innermost such span open over it, and where
  none is open, by the harness's own label (``trace.Labels``).

``reduce(pd, scope_of)`` returns ``{"scopes": {scope: device self
seconds, averaged over the chips}, "idle_by_span": {span or label:
seconds}}``.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

from . import trace

SCOPE_PREFIX = "hop."
SPAN_PREFIX = "serve."
# instructions that run nothing on the device
INERT = ("parameter", "get-tuple-element", "tuple", "constant", "bitcast")

_COMP = re.compile(r"^(?:ENTRY )?%?(?P<name>[\w.\-]+) \(.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%?(?P<name>[\w.\-]+) = (?P<rest>.*)$")
_OP = re.compile(r"\b(?P<op>[a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="(?P<v>[^"]*)"')
_CALLS = re.compile(r"\bcalls=%?(?P<c>[\w.\-]+)")
_REF = re.compile(r"%(?P<r>[\w.\-]+)")


def parse(hlo_text: str) -> dict[str, list[dict]]:
    """{computation: [instruction, ...]} in program order; an instruction
    is ``{"name", "op", "scope", "calls", "refs", "root"}``."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m and not line.startswith(" "):
            cur = comps.setdefault(m["name"], [])
            continue
        m = _INSTR.match(line)
        if m is None or cur is None:
            continue
        rest = m["rest"]
        op = _OP.search(rest)
        name_m = _OP_NAME.search(rest)
        calls = _CALLS.search(rest)
        cur.append({
            "name": m["name"],
            "op": op["op"] if op else "",
            "scope": scope_of_op_name(name_m["v"]) if name_m else None,
            "calls": calls["c"] if calls else None,
            "refs": [r["r"] for r in _REF.finditer(rest.split(
                ", metadata=")[0])],
            "root": line.lstrip().startswith("ROOT "),
        })
    return comps


def scope_of_op_name(op_name: str):
    """The innermost ``hop.*`` component of an ``op_name`` path."""
    scopes = [c for c in op_name.split("/") if c.startswith(SCOPE_PREFIX)]
    return scopes[-1] if scopes else None


def scope_map(hlo_text: str) -> dict[str, str]:
    """{instruction name: scope} for every instruction of the compiled
    program that belongs to a ``hop.*`` scope (module docstring)."""
    comps = parse(hlo_text)
    scope = {}
    for instrs in comps.values():
        for ins in instrs:
            scope[ins["name"]] = ins["scope"]

    def called(comp: str):
        body = comps.get(comp, ())
        roots = [i["scope"] for i in body if i["root"] and i["scope"]]
        rest = [i["scope"] for i in body if i["scope"]]
        return (roots or rest or [None])[0]

    for instrs in comps.values():
        for ins in instrs:
            if scope[ins["name"]] is None and ins["calls"]:
                scope[ins["name"]] = called(ins["calls"])
        local = {i["name"] for i in instrs}
        users = defaultdict(list)
        for ins in instrs:
            for r in ins["refs"]:
                if r in local:
                    users[r].append(ins["name"])
        for ins in reversed(instrs):             # from the consumer
            if scope[ins["name"]] is None:
                scope[ins["name"]] = next(
                    (scope[u] for u in users[ins["name"]] if scope[u]), None)
        for ins in instrs:                       # else from the producer
            if scope[ins["name"]] is None:
                scope[ins["name"]] = next(
                    (scope[r] for r in ins["refs"]
                     if r in local and scope[r]), None)
    return {k: v for k, v in scope.items() if v}


def nest(spans: list[tuple[int, int, str]]) -> list[tuple[int, int, str]]:
    """Nested intervals → disjoint, sorted ``(start, end, innermost
    name)`` pieces; a child that outlasts its parent is cut at its end."""
    out, stack, t = [], [], None

    def emit(lo, hi, name):
        if hi > lo:
            out.append((lo, hi, name))

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            _, end, top = stack.pop()
            emit(t, end, top)
            t = end
        if stack:
            emit(t, s, stack[-1][2])
            e = min(e, stack[-1][1])
        stack.append((s, e, name))
        t = s
    while stack:
        _, end, top = stack.pop()
        emit(t, end, top)
        t = end
    return out


def program_spans(pd) -> list[tuple[int, int, str]]:
    """The mirrored program spans (``serve.*``) on the host plane."""
    cpu = pd.find_plane_with_name("/host:CPU")
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
            for line in (cpu.lines if cpu is not None else ())
            for e in line.events if e.name.startswith(SPAN_PREFIX)]


def split(pieces, starts, lo: int, hi: int):
    """[(name, start, end)] covering [lo, hi) by the disjoint ``pieces``;
    the name is None where no piece lies."""
    out, t = [], lo
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    while t < hi and i < len(pieces):
        s, e, name = pieces[i]
        i += 1
        if e <= t:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((None, t, s))
        out.append((name, max(s, t), min(e, hi)))
        t = min(e, hi)
    if t < hi:
        out.append((None, t, hi))
    return out


def reduce(pd, scope_of: dict[str, str]) -> dict:
    """Device self seconds by scope, and idle seconds by program span
    (module docstring)."""
    spans = trace.host_spans(pd)
    if not spans.get(trace.WINDOW):
        raise ValueError("the trace holds no 'window' annotation")
    lo, hi = spans[trace.WINDOW][0]
    devs = trace.device_ops(pd)
    if not devs:
        raise ValueError("the trace holds no device operation")
    scopes = defaultdict(float)
    idle = defaultdict(float)
    n_dev = len(devs)
    for i, (_, ops) in enumerate(sorted(devs.items())):
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if e > lo and s < hi]
        for text, sec in trace.self_times(inside).items():
            sc = scope_of.get(trace.instruction(text))
            if sc is not None:
                scopes[sc] += sec / n_dev
        if i == 0:
            busy = trace.merge([(s, e) for _, s, e in inside], lo, hi)
            pieces = nest(program_spans(pd))
            starts = [s for s, _, _ in pieces]
            labels = trace.Labels(spans)
            for s, e in trace.gaps(busy, lo, hi):
                for name, a, b in split(pieces, starts, s, e):
                    if name is not None:
                        idle[name] += (b - a) / 1e9
                    else:
                        for label, ns in labels.split(a, b):
                            idle[label] += ns / 1e9
    return {"scopes": dict(scopes), "idle_by_span": dict(idle)}
