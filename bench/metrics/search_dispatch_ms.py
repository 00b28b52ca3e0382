"""Runtime: median host time of one search dispatch, the program's
``search.execute`` span (batch closed to the search step's call
returning: concatenate, pad, host-to-device puts, the state lock, the
chain-budget refresh, the jit call), in ms."""

import numpy as np

from bench import spans


def from_spans(sp):
    from repro.obs.trace import SPAN_SEARCH_EXECUTE

    runs = [b - a for a, b, _ in sp.host.get(SPAN_SEARCH_EXECUTE, ())]
    return float(np.median(runs) / 1e6) if runs else None


def read(ctx):
    sp = spans.load()
    return from_spans(sp) if sp is not None else None
