"""Device queue and transfer: median, per search dispatch, of the time
from the search step's call returning (the end of its ``search.execute``
span) to the answers on the host (the end of its ``search.device_wait``
span), less the device time of its search-program launch, in ms.  What
is left is the launch's wait for the device plus the fetch of the answers.
Both ends are host spans and the launch's length is read on the device's
own clock, so the reading does not depend on the offset between the
host's and the device's clocks in the trace."""

import numpy as np

from bench import spans


def from_spans(sp):
    runs = [(w1 - e1) - (l1 - l0)
            for (_, e1), (l0, l1), (_, w1)
            in spans.search_launch_pairs(sp).values()]
    return float(np.median(runs) / 1e6) if runs else None


def read(ctx):
    sp = spans.load()
    return from_spans(sp) if sp is not None else None
