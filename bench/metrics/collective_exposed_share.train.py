"""Share of the traced window in which a collective runs on a device and no
computation does, averaged over the chips (%)."""


def read(run, peaks):
    if run.trace is None:
        return None
    return 100.0 * run.trace.collective_exposed_s / run.trace.window_s
