"""Forward and backward operations the model needs per token (recomputation
excluded; ``harness/flops.py``) times ``train_tok_s``, over the chips' bf16
peak (%)."""
from bench.harness import train


def read(run, peaks):
    if peaks is None:
        return None
    return 100.0 * train.mfu(run, peaks)
