"""Share of the hop loop's steps that were probes, %: the candidates the
probing engine promoted to its exact tier (``search_probes_total``) over
its probes and expansions (``search_hops_total``).  A program whose engine
records no probes reads nothing."""


def read(run):
    if run.registry is None:
        return None
    if not any(name == "search_probes_total"
               for name, *_ in run.registry.families()):
        return None
    hops = run.registry.counter("search_hops_total").value
    if not hops:
        return None
    return 100.0 * run.registry.counter("search_probes_total").value / hops
