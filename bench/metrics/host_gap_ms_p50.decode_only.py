"""Median device-idle time between consecutive decode-only engine steps,
from the end of one step's device work to the start of the next's, with
steps paired to their device work by the program's own spans
(``harness/spans.py``), in ms."""
import numpy as np

from bench.harness import spans


def read(run, peaks):
    w = spans.analyse(getattr(run, "events", None))
    gaps = spans.host_gaps(w) if w is not None else []
    return float(np.median(gaps)) * 1e-6 if gaps else None
