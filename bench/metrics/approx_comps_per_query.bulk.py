"""RaBitQ distance estimates per answered query
(``search_approx_comps_total``): the probing engine's estimates, one per
fresh neighbour of every expansion."""


def read(run):
    if run.registry is None or not run.n_answers:
        return None
    if not any(name == "search_approx_comps_total"
               for name, *_ in run.registry.families()):
        return None
    return (run.registry.counter("search_approx_comps_total").value
            / run.n_answers)
