"""Host time of the serve loop per batch, in ms: each ``serve.batch`` span
less its ``serve.device_execute`` child (batch formation, padding and the
per-request fan-out), averaged over the window's batches."""


def read(run):
    if run.tracer is None:
        return None
    execs = {s.parent_id: s.duration_s
             for s in run.tracer.by_name("serve.device_execute")}
    host = [b.duration_s - execs[b.span_id]
            for b in run.tracer.by_name("serve.batch") if b.span_id in execs]
    return 1e3 * sum(host) / len(host) if host else None
