"""Seconds of the build's candidate search (device hop loops), summed over
the refinement iterations' ``refine_iter`` build events."""


def read(run):
    if run.registry is None:
        return None
    vals = [e["search_s"] for e in run.registry.events
            if e.get("name") == "build_progress"
            and str(e.get("phase", "")).startswith("refine_iter")]
    return float(sum(vals)) if vals else None
