"""KV pool peak share, %: the most pages referenced by live block tables
(the engine's ``serving_blocks_in_use`` gauge, read after every step in
the window) over the pool's usable pages (all but the scrap page)."""


def read(run):
    if not run.win.pages_in_use:
        return None
    return 100 * max(run.win.pages_in_use) / (run.n_pages - 1)
