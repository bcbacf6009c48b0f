"""Operations and bytes of the parts of one serving step, from the same
shapes and terms as ``counts.py``:

* ``bitlinear``: every BitLinear's 2 K M operations per real token; its
  2-bit planes and 2-byte channel scales;
* ``attention``: QK^T and PV over each token's context; each slot's keys
  and values read and the new ones written, at 2 bytes;
* ``head``: 2 D V operations per emitted row; the head's weight at 2
  bytes.

The parts' operations sum to ``counts.step_counts``' exactly; their bytes
leave out the norms and the embedding rows.
"""
from __future__ import annotations

import counts


def step_parts(config: dict, slots, emit: int) -> dict:
    """{part: (operations, bytes)} of one step of ``config``'s model;
    ``slots`` and ``emit`` as ``counts.step_counts`` takes them."""
    s = counts._dense_shapes(config)
    tokens = sum(n for n, _ in slots)
    linears = s["layers"] * sum(k * m for k, m in s["linears"])
    planes = s["layers"] * sum(k * m / 4 + counts.BF16 * m
                               for k, m in s["linears"])
    context = sum(counts._context_sum(n, e) for n, e in slots)
    attn_ops = 4 * s["layers"] * s["h"] * s["dh"] * context
    kv_token = s["layers"] * 2 * s["kv"] * s["dh"] * counts.BF16
    return {
        "bitlinear": (float(2 * tokens * linears), float(planes)),
        "attention": (float(attn_ops),
                      float(kv_token * sum(e for _, e in slots))),
        "head": (float(2 * emit * s["d"] * s["vocab"]),
                 float(s["d"] * s["vocab"] * counts.BF16)),
    }
