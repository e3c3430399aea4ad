"""90th percentile, over the requests submitted in the window, of the time
from submission to first token (numpy's linear interpolation), in ms."""
import numpy as np

from bench.harness import serve


def read(run, peaks):
    t = serve.ttfts(run)
    return float(np.percentile(t, 90)) * 1e3 if t else None
