"""Model operations of every token emitted in the window (one forward pass
at its position, ``harness/flops.py``) over the chip's bf16 peak times the
window (%).  Prompt positions before the last are not counted."""
from bench.harness import serve


def read(run, peaks):
    if peaks is None:
        return None
    return 100.0 * serve.decode_flops(run) / (peaks.flops * (run.t1 - run.t0))
