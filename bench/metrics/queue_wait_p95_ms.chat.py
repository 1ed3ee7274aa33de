"""Scheduler: 95th percentile, over every request due in the window, of
the time from its due time to the start of the step that took it off
the waiting queue; a request still waiting at the close counts until
then (ms)."""
import stats


def read(rec):
    return 1e3 * stats.percentile(stats.queue_waits(rec), 95)
