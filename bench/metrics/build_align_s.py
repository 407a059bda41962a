"""Seconds of the build's degree alignment (the ``align_degree`` build
event's ``elapsed_s``)."""


def read(run):
    if run.registry is None:
        return None
    vals = [e["elapsed_s"] for e in run.registry.events
            if e.get("name") == "build_progress"
            and e.get("phase") == "align_degree"]
    return float(vals[-1]) if vals else None
