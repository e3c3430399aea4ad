"""Share of the traced window in which the device idles with no ``repro.*``
span open on the engine's main thread and no ``repro.gc`` on any thread
(device times offset to the host's clock by ``harness/spans.py``), in %."""
from bench.harness import spans


def read(run, peaks):
    w = spans.analyse(getattr(run, "events", None))
    if w is None or w.clock.offset is None:
        return None
    _, rest = spans.attribute(run.events, w)
    return 100.0 * rest / w.length_ns
