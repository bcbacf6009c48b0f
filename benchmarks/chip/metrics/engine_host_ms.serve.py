"""Host time of the engine's phases (engine.admit, plan, dispatch, sample,
emit; not the wait on the device) per traced step, in ms."""
import readers


def read(rec):
    return readers.engine_host_ms(rec)
