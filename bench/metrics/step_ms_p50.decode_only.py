"""Median host-clock time of the window's engine steps that ran a decode
step and no prefill chunk, in ms."""
import numpy as np

from bench.harness import serve


def read(run, peaks):
    t = [s.t1 - s.t0 for s in serve.window_steps(run)
         if s.decoded and s.chunks == 0]
    return float(np.median(t)) * 1e3 if t else None
