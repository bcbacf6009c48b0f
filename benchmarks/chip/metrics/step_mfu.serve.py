"""Operations the traced window's real tokens require (counts.py) over the
traced wall time at the int8 peak, in percent."""
import readers


def read(rec):
    return readers.step_mfu(rec)
