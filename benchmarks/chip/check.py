"""Whether what the timed path served is correct.

While the window runs, the driver keeps on the device the program's own
logit of every token it serves (``driver.Driver.served_logits``).  Once the
window has closed and the program's state is freed, a sample of the
requests the run finished, drawn from the seed and always holding the
longest, goes through the plain reference (the ``logits_at`` of the
configuration's architecture file, ``spec.arch``): each prompt with its
served tokens, teacher-forced.  At each served position the token's error
is the distance from the reference's best logit to the program's logit of
the token it served, by way of the reference's logit of that token:
(best - reference[token]) + |reference[token] - program[token]|.  Where
the served token is the reference's choice, as greedy decoding makes it in
a sound run, that is |best - program[token]|, rounding alone.  A token
the reference would not choose adds the reference's own margin, even where
the program's logit of it is as high as a right token's would be: a slot
handed another slot's logits serves a token that is the best of the wrong
row (bitnet2b.chat on one TPU v5e: |best - program[token]| alone read
0.067 with half the batch left out, under the limit of 0.1).  A lost cache
write or a mixed-up row reads as a gap of the logits' own size.  The number
compared is the mean of the squared errors over the sample, ``logit_mse``,
against the cell's limit.

Why the mean square and not the widest error: per-token int8 activation
quantization turns any rounding into flips of single activations, whose
effect does not shrink with the rounding.  So the program's errors and
the control's differ by only about three times at every percentile
(dscoder33b.batch on one TPU v5e: median 0.015 against 0.042, widest
0.08-0.095 against 0.21-0.30), and the square gives the limit room on
both sides.

The control puts the reference in the program's place one precision lower:
at the same positions it serves its own best token, whose error is read
the same way.  ``calibrate.py`` reads both.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

import spec


def sample(finished: list, seed: int, n: int) -> list:
    """``n`` finished requests: the longest, then others drawn from the
    seed."""
    if not finished:
        return []
    total = lambda r: len(r.prompt) + len(r.out_tokens)  # noqa: E731
    longest = max(finished, key=total)
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng([seed, 0x5EED])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _teacher_forced(reqs):
    seqs, rows = [], []
    for r in reqs:
        p = np.asarray(r.prompt, np.int32)
        out = np.asarray(r.out_tokens, np.int32)
        seqs.append(np.concatenate([p, out[:-1]]))
        rows.append(np.arange(len(p) - 1, len(p) - 1 + len(out)))
    return seqs, rows


def errors(config: dict, seed: int, reqs: list, served: dict, pad_to: int,
           low: str | None = None) -> np.ndarray:
    """Per served token, (reference's best logit - its logit of the token)
    + |its logit of the token - the program's logit of the token it served|
    (``served``: uid -> {token index: logit}; a token with no kept logit
    reads NaN).  With ``low``, the control serves its own best token and
    its logit of it takes the program's place."""
    seqs, rows = _teacher_forced(reqs)
    logits_at = spec.arch(config).logits_at
    ref = logits_at(config, seed, seqs, rows, pad_to=pad_to)
    if low is None:
        toks = np.concatenate([np.asarray(r.out_tokens, np.int32)
                               for r in reqs])
        got = np.array([served.get(r.uid, {}).get(t, np.nan)
                        for r in reqs for t in range(len(r.out_tokens))],
                       np.float64)
    else:
        ctrl = logits_at(config, seed, seqs, rows, pad_to=pad_to, low=low)
        toks = np.asarray(jnp.argmax(ctrl, axis=-1), np.int32)
        got = np.asarray(jnp.max(ctrl, axis=-1), np.float64)
    best = np.asarray(jnp.max(ref, axis=-1), np.float64)
    at = np.asarray(jnp.take_along_axis(ref, jnp.asarray(toks)[:, None],
                                        axis=-1)[:, 0], np.float64)
    return (best - at) + np.abs(at - got)


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every number at or under its limit.  A missing number, or one with no
    limit, fails."""
    out = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = (value is not None and limit is not None
                and np.isfinite(value) and value <= limit)
        ok &= bool(good)
        out[name] = {"value": value, "limit": limit}
    return ok and bool(limits), out
