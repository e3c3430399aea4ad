"""Median duration of the decode graph's host-plan run (``repro.plan.run``
inside ``repro.paged.decode``) over the window's decode-only steps: the
host's time to launch every node of the step, in ms."""
import numpy as np

from bench.harness import spans


def read(run, peaks):
    w = spans.analyse(getattr(run, "events", None))
    t = spans.plan_dispatch(w) if w is not None else []
    return float(np.median(t)) * 1e-6 if t else None
