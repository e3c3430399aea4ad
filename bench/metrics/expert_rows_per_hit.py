"""(Token, expert) pairs routed to the experts this chip holds per held
expert that got any, over the window: the window's delta of the engine's
``stats()["n_expert_rows"]`` over that of ``["n_expert_hits"]`` (both
summed over layers and program calls).  None without the counters."""


def read(run, peaks):
    try:
        rows = run.stats1["n_expert_rows"] - run.stats0["n_expert_rows"]
        hits = run.stats1["n_expert_hits"] - run.stats0["n_expert_hits"]
    except KeyError:
        return None
    return rows / hits if hits > 0 else None
