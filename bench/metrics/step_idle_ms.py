"""Host time inside the engine step while the device is idle: each traced
batch's host span (``search_with`` + ``block_until_ready``) less the
device-busy time inside it, mean over the batches wholly inside the traced
slice, ms. For a host-memory rerank store this is the device-side view of
the kappa-row gather and its copy to the device (the program's
``serve.host_take`` and ``serve.host_copy`` spans), with the step's
dispatch and the candidates' copy back."""


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.batches()
    if not spans:
        return None
    idle = [(hi - lo) / 1e9 - run.trace.busy_in(lo, hi)
            for lo, hi in spans.values()]
    return 1e3 * sum(idle) / len(idle)
