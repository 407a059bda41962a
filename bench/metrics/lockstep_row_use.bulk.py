"""Share of the lock-step loop's row slots that did work, %: the
iterations live rows were active in (``search_row_iters_total``) over each
batch's iterations times its bucket rows (``search_slot_iters_total``).
The rest is the lock-step tail: rows done, or pad, while the batch's
slowest query still runs."""


def read(run):
    if run.registry is None:
        return None
    slots = run.registry.counter("search_slot_iters_total").value
    if not slots:
        return None
    return 100.0 * run.registry.counter("search_row_iters_total").value / slots
