"""95th percentile of every gap between two tokens of one request that
ends in the window (ms)."""
import readers


def read(rec):
    return readers.percentile_ms(readers.token_gaps(rec), 95)
