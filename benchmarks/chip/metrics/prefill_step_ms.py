"""Engine wall time in steps that carry prompt tokens over their count,
over the window (step_time_s{phase=prefill} / prefill_steps, ms)."""
import readers


def read(rec):
    return readers.per_step_ms(rec, "prefill")
