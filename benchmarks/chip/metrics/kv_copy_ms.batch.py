"""Device self time under the KV pool's scopes (kv_gather and kv_scatter:
each layer's read through the block table and its row write) per traced
step, in ms."""
import scopes


def read(rec):
    return scopes.pool_ms(rec)
