"""99th percentile of how late the load generator submitted each request
due in the window: submit time minus due time (ms)."""
import readers


def read(rec):
    return readers.percentile_ms(
        [r["submit"] - r["due"] for r in readers.window_requests(rec)], 99)
