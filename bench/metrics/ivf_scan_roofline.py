"""Least time of the IVF fine scan's work (``bench/work.py``) over the
summed device time of the ``ivf_scan`` kernel's events inside the traced
batches' host spans, %."""

# the fine-scan kernel's events in the TPU trace are named by their HLO
# line, "%ivf_scan_topk.<n> = ... custom-call(...)"
KERNEL = r"^%?ivf_scan_topk\b"


def read(run):
    if run.trace is None or not run.work:
        return None
    spans = run.trace.batches()
    least = kernel = 0.0
    for i, w in run.work.items():
        if w["fine"] is None:
            return None
        least += run.peaks.least_s(w["fine"])
        kernel += run.trace.kernel_s(KERNEL, *spans[i])
    return 100.0 * least / kernel if kernel > 0 else None
