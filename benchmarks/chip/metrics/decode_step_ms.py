"""Engine wall time in pure-decode steps over their count, over the window
(step_time_s{phase=decode} / decode_steps, ms)."""
import readers


def read(rec):
    return readers.per_step_ms(rec, "decode")
