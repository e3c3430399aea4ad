"""95th percentile of every gap between consecutive tokens of one request
that ends in the window (numpy's linear interpolation), in ms."""
import numpy as np

from bench.harness import serve


def read(run, peaks):
    g = serve.gaps(run)
    return float(np.percentile(g, 95)) * 1e3 if g else None
