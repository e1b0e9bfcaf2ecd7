"""Code rows the IVF fine scan scores over the distinct code rows it has
to stream, summed over the traced batches (``bench/work.py``), x: how many
of a batch's queries share each probed list's read. ``ops / (2 d)`` is
the rows scored and ``bytes / d`` the distinct rows, so ``d`` cancels."""


def read(run):
    if not run.work:
        return None
    scored = distinct = 0.0
    for w in run.work.values():
        if w["fine"] is None:
            return None
        scored += w["fine"].ops / 2
        distinct += w["fine"].bytes
    return scored / distinct if distinct > 0 else None
