"""Idle device time per batch while the serve loop moves a batch to and
from the chip, ms: the window's idle time under the mirrored
``serve.put``, ``serve.launch`` and ``serve.fetch`` spans
(``idle_by_span``), over the window's batches (``serve.batch`` spans)."""

SPANS = ("serve.put", "serve.launch", "serve.fetch")


def read(run):
    t = run.trace
    idle = (t or {}).get("idle_by_span")
    if not idle or not any(k.startswith("serve.") for k in idle) \
            or run.tracer is None:
        return None
    n = len(run.tracer.by_name("serve.batch"))
    return 1e3 * sum(idle.get(k, 0.0) for k in SPANS) / n if n else None
