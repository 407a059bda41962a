"""Exact distance evaluations per answered query
(``search_dist_comps_total``)."""


def read(run):
    if run.registry is None or not run.n_answers:
        return None
    return (run.registry.counter("search_dist_comps_total").value
            / run.n_answers)
