"""Names on the profiler's clock (``repro.obs.trace``), on the CPU at a tiny
size: the device scopes in the compiled serving step, and the engine's host
spans under a ``jax.profiler`` trace, which follow ``profiler_annotations``
and change no counter and no token."""
import glob
import re
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.models import model_zoo as zoo
from repro.obs import trace as obs_trace
from repro.plan import runtime as plan_runtime
from repro.serving import Request, ServingEngine
from repro.serving import engine as engine_mod

MATMUL_SCOPES = {"bitlinear", "attention", "head"}
SPANS = obs_trace.ENGINE_SPANS + (obs_trace.STEP_SPAN,)
COUNTERS = ("steps", "prefill_tokens", "decode_tokens", "total_tokens",
            "preemptions", "peak_kv_blocks", "max_step_tokens")


@pytest.fixture(scope="module")
def model():
    cfg = configs.get("bitnet-2b-4t").reduced()
    return cfg, zoo.init_params(cfg, jax.random.PRNGKey(0))


def _engine(model, annotations=False):
    cfg, params = model
    return ServingEngine(cfg, params, packed=True, max_len=48, batch_slots=2,
                         prefill_chunk=16, block_size=8,
                         profile_density=False,
                         profiler_annotations=annotations)


def _innermost(op_name: str):
    for part in reversed(op_name.split("/")):
        if part in obs_trace.STEP_SCOPES:
            return part
    return None


def _op_names(hlo: str, op: str | None = None) -> list:
    """The ``op_name`` of every instruction (of opcode ``op``)."""
    out = []
    for line in hlo.splitlines():
        if op is not None and f" {op}(" not in line:
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        out.append(m.group(1) if m else None)
    return out


def test_flat_step_names_its_parts(model):
    eng = _engine(model)
    cfg, w, b = eng.cfg, eng.token_budget, eng.slots
    fn = jax.jit(lambda p, pools, tbl, tk, sl, ps, er:
                 engine_mod._flat_call(cfg, p, pools, tbl, tk, sl, ps, er))
    with plan_runtime.activate(eng.plan):
        lowered = fn.lower(eng.params, eng.kv.pools, eng.kv.table_view(2),
                           jnp.zeros(w, jnp.int32),
                           jnp.full(w, b, jnp.int32),
                           jnp.zeros(w, jnp.int32), jnp.zeros(b, jnp.int32))
    compiled = lowered.compile().as_text()
    found = {_innermost(n) for n in _op_names(compiled) if n}
    assert set(obs_trace.STEP_SCOPES) <= found
    # Every matmul is a BitLinear, attention or the head: in the program
    # handed to XLA, and in the compiled one wherever a dot kept its
    # metadata (the CPU compiler drops it from the dots it rewrites).
    handed = lowered.compiler_ir("hlo").as_hlo_module().to_string()
    dots = _op_names(handed, "dot")
    assert len(dots) == 10          # q, k, v, o, gate, up, down, QK^T, PV, head
    assert {_innermost(n) for n in dots} == MATMUL_SCOPES
    kept = [n for n in _op_names(compiled, "dot") if n]
    assert kept and {_innermost(n) for n in kept} <= MATMUL_SCOPES


def _requests(offset=0):
    # Prompts that fit one chunk: every step emits a token for each slot.
    return [Request(uid=offset + i, prompt=np.arange(6) + 1 + i,
                    max_new_tokens=3) for i in range(2)]


def _traced(eng) -> tuple:
    """Serve two requests under a profiler trace; the steps taken and the
    host spans of the vocabulary recorded, ``(name, start, end,
    step_num)``."""
    eng.run(_requests(100))             # compile outside the trace
    d = tempfile.mkdtemp()
    for r in _requests():
        eng.submit(r)
    jax.profiler.start_trace(d)
    steps = 0
    try:
        while eng.busy:
            steps += eng.step()
    finally:
        jax.profiler.stop_trace()
    pd = jax.profiler.ProfileData.from_file(
        glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0])
    spans = [(e.name, e.start_ns, e.end_ns, dict(e.stats).get("step_num"))
             for plane in pd.planes if plane.name.startswith("/host:CPU")
             for line in plane.lines for e in line.events
             if e.name in SPANS]
    return steps, spans


@pytest.fixture(scope="module")
def traced(model):
    on, off = _engine(model, True), _engine(model, False)
    return {"on": (on, *_traced(on)), "off": (off, *_traced(off))}


def test_engine_spans_once_per_step_and_nested(traced):
    eng, steps, spans = traced["on"]
    assert steps == 3
    first = eng.stats["steps"] - steps
    by_step: dict = {}
    for name, s, e, n in spans:
        by_step.setdefault(int(n), {}).setdefault(name, []).append((s, e))
    assert sorted(by_step) == list(range(first, first + steps))
    for got in by_step.values():
        assert sorted(got) == sorted(SPANS)
        assert all(len(v) == 1 for v in got.values())
        (call,) = got[obs_trace.STEP_SPAN]
        inside = lambda name: (call[0] <= got[name][0][0]  # noqa: E731
                               and got[name][0][1] <= call[1])
        assert inside("engine.dispatch") and inside("engine.wait")
        order = ["engine.admit", "engine.plan", obs_trace.STEP_SPAN,
                 "engine.sample", "engine.emit"]
        bounds = [got[name][0] for name in order]
        assert all(a[1] <= b[0] for a, b in zip(bounds, bounds[1:]))


def test_spans_off_record_nothing_and_change_no_counter(traced):
    on, _, _ = traced["on"]
    off, steps, spans = traced["off"]
    assert steps == 3 and spans == []
    assert {k: on.stats[k] for k in COUNTERS} == \
        {k: off.stats[k] for k in COUNTERS}
    for name in ("planned_tokens", "realized_tokens"):
        assert on.metrics.get(name).value == off.metrics.get(name).value


def test_spans_change_no_token(model):
    on, off = _engine(model, True), _engine(model, False)
    assert [r.out_tokens for r in on.run(_requests())] == \
        [r.out_tokens for r in off.run(_requests())]


def test_span_off_is_the_shared_null_context(model, monkeypatch):
    # Off, each span site reads the flag and enters the shared null
    # context; no span is made.
    def no_span(*args):
        raise AssertionError("a profiler span was made with annotations off")

    monkeypatch.setattr(obs_trace, "profiler_span", no_span)
    reqs = _engine(model, False).run(_requests())
    assert all(r.out_tokens for r in reqs)
    with obs_trace.NULL_SPAN:
        pass
