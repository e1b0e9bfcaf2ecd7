"""Host span of the engine step (``search_with`` + ``block_until_ready``),
averaged over the batches dispatched in the window."""


def read(run):
    spans = [b.t1 - b.t0 for b in run.batches]
    return 1e3 * sum(spans) / len(spans) if spans else None
