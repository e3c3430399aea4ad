"""Share of slot-steps that decoded: decoding rows summed over the window's
decode steps, over slots times decode steps (engine state, %)."""
from bench.harness import serve


def read(run, peaks):
    steps = [s for s in serve.window_steps(run) if s.decoded]
    if not steps:
        return None
    return 100.0 * sum(len(s.ctx) for s in steps) / (run.capacity * len(steps))
