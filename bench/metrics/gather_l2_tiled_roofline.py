"""``gather_l2_tiled``'s share of its roofline, %: the least time the chip
could take for the calls in the window (the larger of their bytes over the
HBM bandwidth and their operations over the peak rate, from each call's
shapes: ``harness/kernels.py``) over the device time of the kernel's
events.  The bytes bound it."""


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    k = run.trace["kernels"].get("gather_l2_tiled")
    if not k or k["seconds"] <= 0:
        return None
    p = run.peaks
    least = max(k["bytes"] / p["hbm_bytes_per_s"],
                k["flops"] / p["bf16_flops_per_s"])
    return 100.0 * least / k["seconds"]
