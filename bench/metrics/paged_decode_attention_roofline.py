"""The paged decode-attention kernel's share of its roofline: the least time
the chip could take for the attention of every decode step in the traced
window (one call per attention layer, at that layer's window;
``harness/flops.py``), over the summed device self time of the kernel's
events (%)."""
from bench.harness import flops, serve

KERNEL = "paged_decode_attention"


def read(run, peaks):
    if run.trace is None or peaks is None:
        return None
    spent = run.trace.time_of(KERNEL)
    if spent <= 0:
        return None
    least = 0.0
    for s in serve.window_steps(run):
        if s.decoded and s.ctx:
            least += flops.paged_decode_attention_least_time(
                run.model, s.ctx, peaks.flops, peaks.hbm_bw)
    return 100.0 * least / spent
