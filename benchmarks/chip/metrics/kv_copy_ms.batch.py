"""Device self time under the kv_gather and kv_scatter scopes per traced
step (pool to view and back), in ms."""
import scopes


def read(rec):
    return scopes.kv_copy_ms(rec)
