"""95th percentile of every wall-clock gap between consecutive output
tokens of a request, both inside the window (ms)."""
import stats


def read(rec):
    return 1e3 * stats.percentile(stats.token_gaps(rec), 95)
