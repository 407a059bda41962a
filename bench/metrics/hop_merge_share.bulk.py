"""Share of the device's busy time in the candidate merge, %: the device
self time of the ops under the ``hop.merge`` named scope over the window's
busy time."""


def read(run):
    t = run.trace
    s = (t or {}).get("scopes")
    if not s or "hop.merge" not in s or t["busy_s"] <= 0:
        return None
    return 100.0 * s["hop.merge"] / t["busy_s"]
