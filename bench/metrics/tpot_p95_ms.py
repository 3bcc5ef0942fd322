"""95th percentile of the time per output token, in ms: (last token's
time - first token's time) / (tokens - 1) of each served request due in
the window, on the host's clock."""
from bench.harness import percentile


def read(run):
    return percentile([1e3 * (r.last - r.first) / (r.tokens - 1)
                       for r in run.served() if r.tokens > 1], 95)
