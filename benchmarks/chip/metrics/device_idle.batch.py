"""Share of the traced window with no operation on the device, in percent."""
import readers


def read(rec):
    return readers.device_idle(rec)
