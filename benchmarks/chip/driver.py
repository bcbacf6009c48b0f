"""Drive ``ServingEngine.step()`` from a host loop, as a server's clients would.

The engine is a synchronous host loop with no server in front, so the
driver submits each request when it falls due and steps the engine between
arrivals.  Open loop: requests fall due on the mix's schedule whatever the
engine does, and a request's ``t_submit`` is its due time, so the engine's
own queue and first-token times count the wait a stall imposes.  Closed
loop: each client sends its next request as soon as its last one finished.

After every step the driver stamps each live request's new tokens with the
host clock; inter-token gaps come from these stamps, so a decode step that
a prefill chunk stalls shows in them.  The driver's own host work is put on
the profiler's clock as ``bench.submit``, ``bench.wait_arrival`` and
``bench.stamp``, with ``bench.step`` around each engine step, inside which
the engine puts ``tsar_engine_step`` around its jitted call.

All times in the record are seconds from the opening of the window.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import spec

# How long after the window closes the driver waits for the first token of
# the last requests due in it, before it counts them as failed.
FIRST_TOKEN_WAIT_S = 60.0


def _span(name: str):
    return jax.profiler.TraceAnnotation(name)


@jax.jit
def _served_logit(logits, tokens):
    return jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]


class Driver:
    def __init__(self, engine, planned, config: dict, *, loop: str,
                 clients: int = 0):
        from repro.serving import Request

        self.engine = engine
        self.config = config
        self.loop = loop
        self.clients = clients
        self.reqs = [Request(uid=p.uid, prompt=p.prompt, max_new_tokens=p.max_new)
                     for p in planned]
        self.due = [p.due for p in planned]
        self.schedule = [p.due for p in planned if p.due is not None]
        self.submit_at = [None] * len(planned)
        self.stamps: list[list[float]] = [[] for _ in planned]
        self.steps: list[dict] = []
        self.kv_in_use: list[int] = []
        self._plan = None
        sched = engine.sched
        plan_flat = sched.plan_flat

        def recording_plan_flat(*args, **kw):
            plan = plan_flat(*args, **kw)
            self._plan = plan
            return plan

        sched.plan_flat = recording_plan_flat

        # The program's logit of each token it serves stays on the device
        # until the window has closed: (logits of the step's rows, [(row,
        # uid, token index)]) per sampling call.
        self.served: list = []
        sample = engine._sample

        def keeping_sample(logits, temps):
            toks = sample(logits, temps)
            plan = self._plan
            owners = [(i, st.req.uid, len(st.req.out_tokens))
                      for i, st in enumerate(engine._slots)
                      if st is not None and plan is not None and plan.emit[i]]
            self.served.append((_served_logit(logits, jnp.asarray(
                toks, jnp.int32)), owners))
            return toks

        engine._sample = keeping_sample

        def restore():
            sched.plan_flat = plan_flat
            engine._sample = sample
        self._restore = restore

    # -- one loop turn ----------------------------------------------------

    def _submit(self, i: int, due_abs: float, now: float) -> None:
        r = self.reqs[i]
        r.t_submit = due_abs
        self.due[i] = due_abs
        self.submit_at[i] = now
        self.engine.submit(r)

    def _step(self) -> None:
        eng = self.engine
        self._plan = None
        t_a = time.perf_counter()
        with _span("bench.step"):
            stepped = eng.step()
        if not stepped and eng.queue_len:
            raise RuntimeError("a queued request cannot be admitted: the KV "
                               "pool is smaller than the admission gate")
        t_b = time.perf_counter()
        plan = self._plan
        if plan is not None and hasattr(plan, "n_real"):
            live = [(int(n), int(ln) + int(n)) for n, ln in
                    zip(plan.n_real, plan.lengths) if n]
            self.steps.append({
                "t0": t_a, "t1": t_b, "width": int(plan.width),
                "view_blocks": int(plan.view_blocks),
                "prefill": int(plan.prefill_tokens),
                "decode": int(plan.decode_tokens),
                "emit": int(np.count_nonzero(plan.emit)), "slots": live})
            self.kv_in_use.append(int(eng.kv.blocks_in_use))
        with _span("bench.stamp"):
            for i in list(self._live):
                r = self.reqs[i]
                st = self.stamps[i]
                while len(st) < len(r.out_tokens):
                    st.append(t_b)
                if r.done:
                    self._live.discard(i)
                    self._finished.append(i)

    # -- the run ------------------------------------------------------------

    def run(self, preroll_s: float, seconds: float, *, on_open=None,
            tracer=None, counters=None) -> dict:
        """Serve from now: ``preroll_s`` of traffic, then the window of
        ``seconds``; then keep serving (open loop) until every request due in
        the window has its first token.  ``on_open`` is called as the window
        opens; ``tracer`` (start, stop), if given, is started and stopped
        between steps inside the window; ``counters()`` snapshots the
        engine's counters at the window's edges."""
        self._live: set[int] = set()
        self._finished: list[int] = []
        t0 = time.perf_counter()
        w0 = t0 + preroll_s
        w1 = w0 + seconds
        snap = {}
        if self.loop == "closed":
            nxt = self.clients
            for i in range(min(self.clients, len(self.reqs))):
                self._submit(i, t0, t0)
                self._live.add(i)
        else:
            nxt = 0
        opened = closed = False
        while True:
            now = time.perf_counter()
            if not opened and now >= w0:
                opened = True
                snap["open"] = counters() if counters else None
                if on_open:
                    on_open()
                if tracer:
                    tracer.plan(w0, w1, [t0 + d for d in self.schedule])
            if not closed and now >= w1:
                closed = True
                snap["close"] = counters() if counters else None
            if tracer and opened:
                tracer.tick(now)
            with _span("bench.submit"):
                if self.loop == "open":
                    while (nxt < len(self.reqs)
                           and t0 + self.due[nxt] <= now):
                        self._submit(nxt, t0 + self.due[nxt], now)
                        self._live.add(nxt)
                        nxt += 1
                else:
                    while self._finished:
                        self._finished.pop()
                        if nxt >= len(self.reqs):
                            raise RuntimeError("closed loop ran out of "
                                               "requests")
                        self._submit(nxt, now, now)
                        self._live.add(nxt)
                        nxt += 1
            if closed and self._window_settled(w0, w1, now):
                break
            if self.engine.busy:
                self._step()
            elif self.loop == "open" and nxt < len(self.reqs):
                with _span("bench.wait_arrival"):
                    time.sleep(max(0.0, t0 + self.due[nxt]
                                   - time.perf_counter()))
            else:
                raise RuntimeError("traffic ran out before the window "
                                   "closed")
        if tracer:
            tracer.tick(float("inf"))
        return self._record(w0, seconds, snap)

    def _window_settled(self, w0: float, w1: float, now: float) -> bool:
        if now > w1 + FIRST_TOKEN_WAIT_S:
            return True
        return all(self.stamps[i] for i, d in enumerate(self.due)
                   if d is not None and self.submit_at[i] is not None
                   and w0 <= d < w1)

    def _record(self, w0: float, seconds: float, snap: dict) -> dict:
        rel = lambda t: None if t is None else t - w0  # noqa: E731
        reqs = []
        for i, r in enumerate(self.reqs):
            if self.submit_at[i] is None:
                continue
            reqs.append({
                "uid": r.uid, "due": rel(self.due[i]),
                "submit": rel(self.submit_at[i]), "admit": rel(r.t_admit),
                "stamps": [t - w0 for t in self.stamps[i]],
                "prompt_len": int(len(r.prompt)), "max_new": r.max_new_tokens,
                "done": bool(r.done)})
        steps = []
        step_counts = spec.arch(self.config).step_counts
        for s, kv in zip(self.steps, self.kv_in_use):
            ops, nbytes = step_counts(self.config, s["slots"], s["emit"])
            steps.append({**s, "t0": s["t0"] - w0, "t1": s["t1"] - w0,
                          "kv_blocks": kv, "ops": ops, "bytes": nbytes})
        return {"window_s": seconds, "loop": self.loop, "requests": reqs,
                "steps": steps, "counters": snap}

    def drain(self) -> None:
        """Serve what is left, untimed, and hand the engine back idle."""
        while self.engine.busy:
            self.engine.step()
        self._restore()

    def finished(self) -> list:
        """Requests served to their end, with their prompts and tokens."""
        return [r for r in self.reqs if r.done and r.out_tokens]

    def served_logits(self) -> dict:
        """uid -> {token index: the program's logit of the token it served},
        fetched from the device in one copy."""
        if not self.served:
            return {}
        vals = np.asarray(jnp.stack([v for v, _ in self.served]))
        out: dict = {}
        for k, (_, owners) in enumerate(self.served):
            for row, uid, t in owners:
                out.setdefault(uid, {})[t] = float(vals[k, row])
        return out
