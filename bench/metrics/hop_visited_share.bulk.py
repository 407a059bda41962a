"""Share of the device's busy time in the visited bitset, %: the device
self time of the ops under the ``hop.visited`` named scope (test, dedup,
set, and the faithful prune's clear) over the window's busy time."""


def read(run):
    t = run.trace
    s = (t or {}).get("scopes")
    if not s or "hop.visited" not in s or t["busy_s"] <= 0:
        return None
    return 100.0 * s["hop.visited"] / t["busy_s"]
