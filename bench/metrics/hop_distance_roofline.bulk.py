"""The exact distance layer's share of its roofline, %, whatever computes
the distances: the least time the chip could take for the distances the
loop had to compute over the device self time of the ops under the
``hop.distance`` named scope.

The least bytes: for each exact distance (``search_dist_comps_total``) its
row of ``d`` f32 read, its id read and its distance written; and each
answered query's ``d`` f32 read once.  The HBM rate bounds it (a subtract,
a multiply and an add per element is far below the peak rate)."""


def read(run):
    t = run.trace
    s = (t or {}).get("scopes")
    if (not s or not s.get("hop.distance") or run.peaks is None
            or run.registry is None):
        return None
    comps = run.registry.counter("search_dist_comps_total").value
    if not comps:
        return None
    d = run.cell.config["dim"]
    least = (comps * (4 * d + 4 + 4) + run.n_answers * 4 * d) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / s["hop.distance"]
