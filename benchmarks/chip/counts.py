"""Operations and bytes that one serving step requires, from the model's
shapes and the step's real tokens.  One function pair per model family.

A step is a list of ``(n, end)`` pairs, one for each slot that lands
tokens: ``n`` real tokens at positions ``end - n .. end - 1``, plus
``emit``, the rows that reach the head.

Operations (a multiply-add counts two):

* every BitLinear: 2 K M per real token;
* attention: for a token at position p, 2 H Dh (p + 1) for QK^T and as much
  for PV, in each layer;
* the head: 2 D V per emitted row.

Bytes are the least any program must move, at the published model's
precisions whatever the program stores: ternary weights at 2 bits plus a
2-byte scale per output channel, norm weights, embedding rows and the head
at 2 bytes, each slot's earlier keys and values read once and the new ones
written once, at 2 bytes.  A program that keeps wider types moves more, so
its share of this bound can only read lower, never above 100%.
"""
from __future__ import annotations

BF16 = 2


def _dense_shapes(c: dict) -> dict:
    d, ff = c["hidden_size"], c["intermediate_size"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or d // h
    linears = [(d, h * dh), (d, kv * dh), (d, kv * dh), (h * dh, d),
               (d, ff), (d, ff), (ff, d)]
    return {"d": d, "h": h, "kv": kv, "dh": dh, "layers": c["num_hidden_layers"],
            "vocab": c["vocab_size"], "linears": linears}


def _context_sum(n: int, end: int) -> int:
    """Sum over positions end-n .. end-1 of (position + 1)."""
    return n * end - n * (n - 1) // 2


def dense_ops(c: dict, slots, emit: int) -> float:
    s = _dense_shapes(c)
    tokens = sum(n for n, _ in slots)
    per_token = 2 * s["layers"] * sum(k * m for k, m in s["linears"])
    attn = 4 * s["layers"] * s["h"] * s["dh"] * sum(_context_sum(n, e)
                                                    for n, e in slots)
    head = 2 * emit * s["d"] * s["vocab"]
    return float(tokens * per_token + attn + head)


def dense_bytes(c: dict, slots, emit: int) -> float:
    s = _dense_shapes(c)
    tokens = sum(n for n, _ in slots)
    planes = s["layers"] * sum(k * m / 4 + BF16 * m for k, m in s["linears"])
    norms = (2 * s["layers"] + 1) * s["d"] * BF16
    embed = tokens * s["d"] * BF16
    head = s["d"] * s["vocab"] * BF16
    kv_token = s["layers"] * 2 * s["kv"] * s["dh"] * BF16
    kv = kv_token * sum(e for _, e in slots)   # e - n read, n written
    return float(planes + norms + embed + head + kv)


FAMILIES = {"bitnet": (dense_ops, dense_bytes),
            "llama": (dense_ops, dense_bytes)}


def step_counts(config: dict, slots, emit: int) -> tuple[float, float]:
    """(operations, bytes) of one step of ``config``'s model."""
    ops, nbytes = FAMILIES[config["model_type"]]
    return ops(config, slots, emit), nbytes(config, slots, emit)
