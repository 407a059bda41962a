"""99th percentile, over the window's batches, of the lock-step loop
iterations each batch ran (the ``iters`` attribute of its ``serve.batch``
span): the loop runs until the batch's slowest query is done."""

import numpy as np


def read(run):
    if run.tracer is None:
        return None
    iters = [s.attrs["iters"] for s in run.tracer.by_name("serve.batch")
             if s.attrs.get("iters") is not None]
    return float(np.percentile(iters, 99)) if iters else None
