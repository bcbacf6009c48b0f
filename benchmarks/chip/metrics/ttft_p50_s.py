"""Median over every request due in the window of first-token time minus
due time (s)."""
import readers
import stats


def read(rec):
    return stats.percentile(readers.ttfts(rec), 50)
