"""Output tokens emitted in the window over the window's length."""
from bench.harness import serve


def read(run, peaks):
    return serve.window_tokens(run) / (run.t1 - run.t0)
