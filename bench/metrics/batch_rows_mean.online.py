"""Requests per dispatched batch, mean over the window (the serve loop's
``serve_batch_size`` histogram)."""


def read(run):
    if run.registry is None:
        return None
    h = run.registry.histogram("serve_batch_size")
    return h.mean if h.count else None
