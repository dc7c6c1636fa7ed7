"""Scheduler: 90th percentile of the wait from a request's due time to the
start of its prefill (its admission); a request not admitted when the
window closes counts with its wait so far."""
import numpy as np


def read(run):
    t1, due = run.win["t1"], run.win["due"]
    waits = [run.rec.admitted.get(q.rid, t1) - due[q.rid]
             for q in run.planned]
    return float(np.percentile(waits, 90)) if waits else None
