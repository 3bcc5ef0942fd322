"""Mean share of the KV arena's pages leased, read after each step of
the window that held work (%)."""


def read(run):
    use = [s.pages_in_use for s in run.window_steps() if s.busy]
    return 100.0 * sum(use) / len(use) if use else None
