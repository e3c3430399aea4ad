"""Share of the traced window in which the device idles while the engine's
main thread waits for the sampled tokens (innermost span
``repro.paged.readback``, device times offset to the host's clock by
``harness/spans.py``), in %."""
from bench.harness import spans


def read(run, peaks):
    w = spans.analyse(getattr(run, "events", None))
    if w is None or w.clock.offset is None:
        return None
    by, _ = spans.attribute(run.events, w)
    return 100.0 * by.get(spans.READBACK, 0.0) / w.length_ns
