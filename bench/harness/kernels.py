"""Operations and bytes of a kernel call, computed from its shapes.

The shapes come from the HLO text of the call: each Pallas call is a
``custom-call`` instruction whose name starts with the kernel's name and
whose operand shapes are written out in its ``operand_layout_constraints``.
The device trace names each op event by that text, so every event gets the
work of its own call.
"""

from __future__ import annotations

import re

_INSTR = re.compile(r"^\s*%?(?P<name>[\w.\-]+) = (?P<out>\S+) custom-call\(")
_SHAPE = re.compile(r"(?P<dtype>[a-z]+\d*)\[(?P<dims>[\d,]*)\]")


def _shapes(text: str) -> list[tuple[str, tuple[int, ...]]]:
    return [(m["dtype"], tuple(int(x) for x in m["dims"].split(",") if x))
            for m in _SHAPE.finditer(text)]


def custom_calls(hlo_text: str, prefix: str) -> dict[str, list]:
    """{instruction name: [operand (dtype, shape), ...]} for every
    custom-call whose instruction name starts with ``prefix``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m or not m["name"].startswith(prefix):
            continue
        start = line.find("operand_layout_constraints={")
        if start < 0:
            continue
        body = line[start + len("operand_layout_constraints={"):]
        body = body[: body.find("}}") + 1]
        out[m["name"]] = _shapes(body)
    return out


def gather_l2_tiled_work(operands: list) -> dict:
    """Work of one ``gather_l2_tiled`` call: operands are the blocked ids
    ``s32[B, T, 1, R]``, the corpus ``f32[n, d]`` (left in HBM; only the
    gathered rows are read) and the queries ``f32[B, 1, d]``.

    bytes: the B·T·R gathered rows of d floats, the queries, the ids read
    and the distances written.  flops: a subtract, a multiply and an add
    per gathered element.
    """
    (_, ids), (_, base), (_, q) = operands
    rows = 1
    for x in ids:
        rows *= x
    d = base[1]
    b = q[0]
    return {"bytes": rows * d * 4 + b * d * 4 + rows * 4 + rows * 4,
            "flops": 3 * rows * d}


WORK = {"gather_l2_tiled": gather_l2_tiled_work}
