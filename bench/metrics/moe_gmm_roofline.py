"""The grouped expert matmul's share of its roofline: the least time the
chip could take for the expert layers of every decode step and prefill
chunk in the traced window, over the summed device self time of the
kernel's events (%).

The least time counts what the work needs, from the configuration's sizes
(``d_model`` d, ``d_ff`` f) and the engine's counters, as window deltas:

- operations: 6 d f for each (token, expert) pair routed to a held expert
  (gate, up and down products), ``stats()["n_expert_rows"]`` (summed over
  layers and calls);
- bytes: in each layer call, the three d x f matrices of every held expert
  that got a row, read once (``stats()["n_expert_hits"]``; an expert that
  got none is not read), and each pair's row read in and written out once
  (d each), all in bfloat16.

Every call of ``mellum2.code_decode`` is bound by its bytes, so the
window's operations and bytes are set against the peaks as one sum.
"""
KERNEL = "moe_gmm"
BF16 = 2


def cost(m: dict, rows: int, hits: int) -> tuple[float, float]:
    """(operations, bytes) of expert layer calls that routed ``rows`` pairs
    to ``hits`` held experts, summed over layers and calls."""
    d, f = m["d_model"], m["d_ff"]
    return 6.0 * d * f * rows, float((hits * 3 * d * f + rows * 2 * d) * BF16)


def read(run, peaks):
    if run.trace is None or peaks is None:
        return None
    spent = run.trace.time_of(KERNEL)
    try:
        rows = run.stats1["n_expert_rows"] - run.stats0["n_expert_rows"]
        hits = run.stats1["n_expert_hits"] - run.stats0["n_expert_hits"]
    except KeyError:
        return None
    if spent <= 0 or rows <= 0:
        return None
    flops, nbytes = cost(run.model, rows, hits)
    return 100.0 * max(flops / peaks.flops, nbytes / peaks.hbm_bw) / spent
