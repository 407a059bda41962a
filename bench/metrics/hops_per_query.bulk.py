"""Hop-loop expansions per answered query (``search_hops_total``)."""


def read(run):
    if run.registry is None or not run.n_answers:
        return None
    return run.registry.counter("search_hops_total").value / run.n_answers
