"""Least time of the traced batches' required work (``bench/work.py``)
over the device's busy time inside their host spans, %."""


def read(run):
    if run.trace is None or not run.work:
        return None
    spans = run.trace.batches()
    least = busy = 0.0
    for i, w in run.work.items():
        least += run.peaks.least_s(w["step"])
        busy += run.trace.busy_in(*spans[i])
    return 100.0 * least / busy if busy > 0 else None
