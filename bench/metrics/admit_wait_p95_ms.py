"""95th percentile of the wait for admission, in ms: from each request's
due time to the start of the first executor step that holds it, over
the requests due in the window that were admitted."""
from bench.harness import percentile


def read(run):
    return percentile([1e3 * (r.admitted - r.due)
                       for r in run.due_in_window()
                       if r.admitted is not None], 95)
