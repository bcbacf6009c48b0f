"""One generator for every traffic mix.

A mix (``traffic/<mix>.json``) is data: the loop kind, the arrival law and
the length laws.  The cell (``cells/<workload>.json``) fixes the offered
rate (open loop) or the number of clients (closed loop).

Every seed gets the same work.  Arrival times and lengths are drawn once
from the mix's ``draw_seed``, in one order, so every seed sends requests of
the same lengths at the same moments; the run's ``--seed`` draws their
token ids (and the model's weights).  A seed that only reordered the
lengths would still change the work: with some twenty requests in a
window, which prompt lands in a burst sets the TTFT tail (bitnet2b.chat on
one TPU v5e: 1.3 s to 2.4 s over six seeds, 4% apart on reruns of one).

The samplers are those of ``benchmarks/workloads/generator.py`` with two
changes: lognormal lengths take their median (``exp`` of the underlying
normal's mean), and rates are requests per wall-clock second.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

# Requests drawn for a closed loop: each client takes the next one when its
# last finishes, and a run must never run out.
CLOSED_POOL = 1024
# Arrivals past the window's end keep the load on while the last requests
# due in the window get their first token.
TAIL_S = 10.0


@dataclass
class Planned:
    uid: int
    due: float | None           # seconds after the traffic starts; None: closed
    prompt: np.ndarray          # (S,) int32
    max_new: int


def lengths(law: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    kind = law["kind"]
    if kind == "fixed":
        out = np.full(n, int(law["value"]))
    elif kind == "uniform":
        out = rng.integers(int(law["lo"]), int(law["hi"]) + 1, size=n)
    elif kind == "lognormal":
        raw = rng.lognormal(math.log(float(law["median"])),
                            float(law["sigma"]), size=n)
        out = np.clip(np.round(raw), int(law.get("lo", 1)), int(law["hi"]))
    elif kind == "choice":
        vals = np.asarray(law["values"], np.int64)
        w = law.get("weights")
        p = None if w is None else np.asarray(w, float) / np.sum(w)
        out = rng.choice(vals, size=n, p=p)
    else:
        raise ValueError(f"unknown length law {kind!r}")
    out = out.astype(np.int64)
    if (out < 1).any():
        raise ValueError(f"{kind} length law produced a length < 1")
    return out


def shortest(law: dict) -> int:
    """The shortest length ``law`` can draw."""
    kind = law["kind"]
    if kind == "fixed":
        return int(law["value"])
    if kind == "choice":
        return int(min(law["values"]))
    return int(law.get("lo", 1))


def gaps(law: dict, n: int, rate: float, rng: np.random.Generator) -> np.ndarray:
    """``n`` gaps between arrivals, scaled so that they sum to exactly
    ``n / rate`` seconds: the offered rate is the cell's, whatever the draw."""
    if rate <= 0:
        raise ValueError(f"arrival rate must be > 0, got {rate}")
    kind = law["kind"]
    if kind == "uniform":
        g = np.full(n, 1.0)
    elif kind == "poisson":
        g = rng.exponential(1.0, size=n)
    elif kind == "gamma":
        cv = float(law["cv"])
        g = rng.gamma(shape=cv, scale=1.0 / cv, size=n)
    else:
        raise ValueError(f"unknown arrival law {kind!r}")
    return g * (n / rate) / g.sum()


def plan(traffic: dict, settings: dict, seed: int, seconds: float,
         vocab: int) -> list[Planned]:
    """The requests of one run, in the order they are due (open loop) or
    handed to clients (closed loop)."""
    draw = np.random.default_rng(int(traffic.get("draw_seed", 0)))
    if traffic["loop"] == "open":
        rate = float(settings["rate_rps"])
        n = math.ceil(rate * (float(settings["preroll_s"]) + seconds + TAIL_S))
        due = np.cumsum(gaps(traffic["arrival"], n, rate, draw))
    elif traffic["loop"] == "closed":
        n, due = CLOSED_POOL, None
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    plens = lengths(traffic["prompt_len"], n, draw)
    olens = lengths(traffic["output_len"], n, draw)
    run = np.random.default_rng(seed)
    return [Planned(uid=i, due=None if due is None else float(due[i]),
                    prompt=run.integers(0, vocab, size=int(plens[i]),
                                        dtype=np.int32),
                    max_new=int(olens[i]))
            for i in range(n)]
