"""Mean share of the executor's slots occupied, as its ``step()``
reports it, over the steps of the window that held work (%)."""


def read(run):
    occ = [s.occupancy for s in run.window_steps() if s.busy]
    return 100.0 * sum(occ) / len(occ) if occ else None
