"""Tokens of every train step completed in the window over the window's
length (from its start to the end of its last step)."""
from bench.harness import train


def read(run, peaks):
    return train.window_tokens_per_s(run)
