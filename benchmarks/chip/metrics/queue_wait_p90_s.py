"""90th percentile over requests due in the window of first admission
into a slot minus due time (s)."""
import readers
import stats


def read(rec):
    return stats.percentile([r["admit"] - r["due"]
                             for r in readers.window_requests(rec)
                             if r["admit"] is not None], 90)
