"""Share of the lock-step slots that the compacting hop loop ran, %: the
row slots the loop held (``search_resident_iters_total``, the sum of
``SearchResult.n_resident``) over each batch's iterations times its bucket
rows (``search_slot_iters_total``).  100% is a loop that keeps every row
to the end; the loop that compacts its batch as rows finish reads less.  A
program without the counter reads nothing."""


def read(run):
    if run.registry is None:
        return None
    slots = run.registry.counter("search_slot_iters_total").value
    resident = run.registry.counter("search_resident_iters_total").value
    if not slots or not resident:
        return None
    return 100.0 * resident / slots
