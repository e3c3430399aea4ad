"""Compilations (and loads from the persistent compilation cache) during the
window, from the engine's process-wide count ``stats()["n_compiles"]``."""


def read(run, peaks):
    a = run.stats0.get("n_compiles")
    b = run.stats1.get("n_compiles")
    return None if a is None or b is None else b - a
