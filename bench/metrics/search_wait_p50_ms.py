"""Runtime: median time a served search waits before its dispatch, the
runtime's own spans admission + queue + batch_form, in ms.  Read from the
request traces, which the traced run samples at rate 1."""

import numpy as np

WAIT = ("admission", "queue", "batch_form")


def read(ctx):
    waits = [sum(s["dur_s"] for s in t["spans"] if s["stage"] in WAIT)
             for t in ctx.traces if t["kind"] == "search"
             and t["outcome"] == "ok"]
    return float(np.percentile(waits, 50) * 1e3) if waits else None
