"""Seconds of the build's host graph surgery (reverse edges and
connectivity repair), summed over the ``refine_iter`` build events."""


def read(run):
    if run.registry is None:
        return None
    vals = [e["surgery_s"] for e in run.registry.events
            if e.get("name") == "build_progress"
            and str(e.get("phase", "")).startswith("refine_iter")]
    return float(sum(vals)) if vals else None
