"""95th percentile of the time to the first token, in ms: from each
served request's due time to the poll that brought its first token to
the host, over the served requests due in the window."""
from bench.harness import percentile


def read(run):
    return percentile([1e3 * (r.first - r.due) for r in run.served()], 95)
