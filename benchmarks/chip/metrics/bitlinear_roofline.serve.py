"""Least time of the BitLinears over the traced steps (their operations at
the int8 peak or their planes at HBM bandwidth, per step) over the device
self time under the bitlinear scope, in percent."""
import scopes


def read(rec):
    return scopes.part_roofline(rec, "bitlinear")
