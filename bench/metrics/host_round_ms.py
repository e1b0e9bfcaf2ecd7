"""Host time of the dispatcher's round outside its engine step: each
``bench.drain`` span (one ``ServingFrontend.drain_once``) less the
``bench.batch`` span inside it (``search_with`` + ``block_until_ready``),
mean over the rounds wholly inside the traced slice, ms. The same
interval as the program's ``serve.round`` less its ``serve.step``, read
from the benchmark's spans, which the trace reduction keeps."""


def read(run):
    if run.trace is None:
        return None
    t = run.trace
    drains = [(e.start_ns, e.end_ns) for e in t.spans
              if e.name == "bench.drain" and e.start_ns >= t._lo
              and e.end_ns <= t._hi]
    if not drains:
        return None
    batches = list(t.batches().values())
    own = [(hi - lo) - sum(min(hi, e) - max(lo, s) for s, e in batches
                           if s < hi and e > lo)
           for lo, hi in drains]
    return sum(own) / len(own) / 1e6
