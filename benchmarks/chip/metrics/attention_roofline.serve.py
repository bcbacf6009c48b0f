"""Least time of attention over the traced steps (QK^T and PV at the bf16
peak or KV bytes at HBM bandwidth, per step) over the device self time
under the attention scope, in percent."""
import scopes


def read(rec):
    return scopes.part_roofline(rec, "attention")
