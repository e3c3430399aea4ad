"""Prompt tokens mapped from cached pages over prompt tokens admitted in the
window: the engine's shared-page counter (``stats()`` delta) times the page
size, over the prompt lengths of the requests admitted (%)."""


def read(run, peaks):
    shared = run.stats1["n_shared_pages"] - run.stats0["n_shared_pages"]
    admitted = sum(run.prompt_len[i] for i in run.admitted)
    if not admitted:
        return None
    return 100.0 * shared * run.page_size / admitted
