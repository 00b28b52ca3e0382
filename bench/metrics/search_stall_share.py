"""Runtime: share of the traced window in which a search is admitted and
unanswered while the search program is not running on the device.  Each
search dispatch covers the time from its oldest request's arrival
(``oldest_ns`` of its ``search.execute`` span, mapped onto the trace's
clock through that span's ``t_ns``) to the end of its ``search.ack``
span; dispatches not wholly inside the window are left out.  A
dispatch's launch of the search program runs inside that interval, so
the reader takes the launches' device time off the covered time instead
of laying device intervals over host ones: the reading does not depend
on the offset between the host's and the device's clocks in the trace."""

from bench import spans
from bench.trace_reduce import _union


def from_spans(sp):
    from repro.obs.trace import SPAN_SEARCH_ACK, SPAN_SEARCH_EXECUTE

    w0, w1 = sp.window
    acks = sp.dispatches(SPAN_SEARCH_ACK)
    pairs = spans.search_launch_pairs(sp)
    iv, busy = [], 0
    for seq, (e0, _, st) in sp.dispatches(SPAN_SEARCH_EXECUTE).items():
        if seq not in acks or "oldest_ns" not in st or "t_ns" not in st:
            continue
        a, b = st["oldest_ns"] + (e0 - st["t_ns"]), acks[seq][1]
        if w0 <= a and b <= w1:
            iv.append((a, b))
            if seq in pairs:
                l0, l1 = pairs[seq][1]
                busy += l1 - l0
    if not iv:
        return None
    covered = sum(b - a for a, b in _union(iv))
    return (covered - busy) / (w1 - w0)


def read(ctx):
    sp = spans.load()
    return from_spans(sp) if sp is not None else None
