"""Runtime: mean number of queries per search dispatch, the ``n`` of the
program's ``search.execute`` spans."""

import numpy as np

from bench import spans


def from_spans(sp):
    from repro.obs.trace import SPAN_SEARCH_EXECUTE

    n = [st["n"] for _, _, st in sp.host.get(SPAN_SEARCH_EXECUTE, ())
         if "n" in st]
    return float(np.mean(n)) if n else None


def read(ctx):
    sp = spans.load()
    return from_spans(sp) if sp is not None else None
