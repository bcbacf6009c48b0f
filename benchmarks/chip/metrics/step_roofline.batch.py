"""Least time of the traced steps (ops at the int8 peak or bytes at HBM
bandwidth, per step) over the step program's device time, in percent."""
import readers


def read(rec):
    return readers.step_roofline(rec)
