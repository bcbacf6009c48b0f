"""Most KV pool blocks in use after any step in the window, over the
pool's blocks, in percent."""
import readers


def read(rec):
    used = [s["kv_blocks"] for s in rec["steps"] if readers.in_window(rec, s["t1"])]
    return 100.0 * max(used) / rec["pool_blocks"] if used else None
