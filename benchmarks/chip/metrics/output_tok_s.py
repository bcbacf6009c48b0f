"""Output tokens emitted in the window over the window's wall time."""
import readers


def read(rec):
    n = sum(1 for r in rec["requests"] for t in r["stamps"]
            if readers.in_window(rec, t))
    return n / rec["window_s"]
