"""Real tokens over planned step rows, over the window (engine counters
realized_tokens / planned_tokens), in percent."""
import readers


def read(rec):
    planned = readers.counter_delta(rec, "planned_tokens")
    if not planned:
        return None
    return 100.0 * readers.counter_delta(rec, "realized_tokens") / planned
