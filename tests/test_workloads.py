"""Property tests for the trace-driven workload generator
(``benchmarks.workloads``): same-seed byte-identity, sampler statistics,
declared shared-prefix structure, and trace serialization round-trips.

These are generator-only tests (no engine, no jax) — the replay integration
lives in ``tests/test_bench_report.py``.
"""
import os
import tempfile

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from benchmarks.workloads import generator
from benchmarks.workloads.generator import WorkloadSpec, generate, preset
from benchmarks.workloads.trace import TRACE_VERSION, Trace

PRESETS = sorted(generator.WORKLOADS)


# ---------------------------------------------------------------------------
# determinism / identity
# ---------------------------------------------------------------------------

class TestDeterminism:
    @settings(max_examples=10, deadline=None)
    @given(name=st.sampled_from(PRESETS),
           seed=st.integers(min_value=0, max_value=2**20),
           quick=st.booleans())
    def test_same_seed_byte_identical(self, name, seed, quick):
        """Trace identity is (name, quick, seed): two generator runs must
        produce byte-identical canonical JSON (and thus fingerprints)."""
        a = generate(preset(name, quick=quick, seed=seed))
        b = generate(preset(name, quick=quick, seed=seed))
        assert a.to_json() == b.to_json()
        assert a.fingerprint() == b.fingerprint()

    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**20))
    def test_seed_shifts_trace(self, seed):
        a = generate(preset("steady", seed=seed))
        b = generate(preset("steady", seed=seed + 1))
        assert a.fingerprint() != b.fingerprint()

    def test_quick_halves_but_keeps_at_least_two(self):
        for name in PRESETS:
            full = preset(name).n_requests
            quick = preset(name, quick=True).n_requests
            assert 2 <= quick <= full


# ---------------------------------------------------------------------------
# sampler statistics
# ---------------------------------------------------------------------------

class TestSamplers:
    N = 4000  # large-sample checks: tolerances are ~10 standard errors

    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**16),
           rate=st.floats(min_value=0.25, max_value=2.0))
    def test_poisson_mean_gap(self, seed, rate):
        rng = np.random.default_rng(seed)
        t = generator._arrivals({"kind": "poisson", "rate": rate}, self.N, rng)
        gaps = np.diff(t)
        assert (gaps >= 0).all()
        assert abs(gaps.mean() - 1.0 / rate) < 0.15 / rate

    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**16),
           cv=st.floats(min_value=0.1, max_value=2.0))
    def test_gamma_mean_gap_independent_of_cv(self, seed, cv):
        """The cv knob reshapes burstiness but must preserve the rate."""
        rng = np.random.default_rng(seed)
        t = generator._arrivals({"kind": "gamma", "rate": 0.5, "cv": cv},
                                self.N, rng)
        assert abs(np.diff(t).mean() - 2.0) < 2.0 * 0.15 / min(1.0, cv)**0.5

    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**16),
           lo=st.integers(min_value=1, max_value=8),
           width=st.integers(min_value=1, max_value=60))
    def test_uniform_length_mean_and_bounds(self, seed, lo, width):
        hi = lo + width
        rng = np.random.default_rng(seed)
        out = generator._lengths({"kind": "uniform", "lo": lo, "hi": hi},
                                 self.N, rng)
        assert out.min() >= lo and out.max() <= hi
        assert abs(out.mean() - (lo + hi) / 2) < 0.05 * width + 0.25

    def test_lognormal_clipped_to_bounds(self):
        rng = np.random.default_rng(0)
        out = generator._lengths(
            {"kind": "lognormal", "mean": 3.0, "sigma": 0.6,
             "lo": 4, "hi": 96}, self.N, rng)
        assert out.min() >= 4 and out.max() <= 96

    def test_choice_draws_only_declared_values(self):
        rng = np.random.default_rng(0)
        vals = [5, 9, 48, 12]
        out = generator._lengths({"kind": "choice", "values": vals}, 200, rng)
        assert set(out.tolist()) <= set(vals)

    def test_burst_all_arrive_at_zero(self):
        rng = np.random.default_rng(0)
        assert (generator._arrivals({"kind": "burst"}, 16, rng) == 0).all()

    def test_arrivals_start_at_zero_and_are_monotone(self):
        for kind in ("uniform", "poisson", "gamma"):
            rng = np.random.default_rng(1)
            t = generator._arrivals({"kind": kind, "rate": 0.7, "cv": 0.3},
                                    100, rng)
            assert t[0] == 0.0
            assert (np.diff(t) >= 0).all()

    def test_bad_specs_rejected(self):
        rng = np.random.default_rng(0)
        with pytest.raises(ValueError):
            generator._arrivals({"kind": "weird"}, 4, rng)
        with pytest.raises(ValueError):
            generator._arrivals({"kind": "poisson", "rate": 0}, 4, rng)
        with pytest.raises(ValueError):
            generator._arrivals({"kind": "gamma", "rate": 1, "cv": 0}, 4, rng)
        with pytest.raises(ValueError):
            generator._lengths({"kind": "weird"}, 4, rng)
        with pytest.raises(ValueError):
            preset("no-such-workload")


# ---------------------------------------------------------------------------
# shared-prefix structure
# ---------------------------------------------------------------------------

class TestSharedPrefix:
    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**20),
           groups=st.integers(min_value=1, max_value=4),
           prefix_len=st.integers(min_value=4, max_value=24),
           fraction=st.floats(min_value=0.3, max_value=1.0))
    def test_declared_structure_holds(self, seed, groups, prefix_len,
                                      fraction):
        """Every request's recorded (prefix_group, prefix_len) must match
        the actual token structure: group members share exactly the leading
        prefix and always carry a fresh tail token."""
        spec = WorkloadSpec(
            name="sp-prop", n_requests=24,
            arrival={"kind": "uniform", "rate": 1.0},
            prompt_len={"kind": "fixed", "value": prefix_len + 8},
            output_len={"kind": "fixed", "value": 2},
            shared_prefix={"groups": groups, "prefix_len": prefix_len,
                           "fraction": fraction},
            seed=seed)
        tr = generate(spec)
        by_group = {}
        for r in tr.requests:
            if r.prefix_group < 0:
                assert r.prefix_len == 0
                continue
            assert 0 <= r.prefix_group < groups
            assert r.prefix_len == prefix_len
            assert len(r.prompt) > prefix_len
            by_group.setdefault(r.prefix_group, []).append(r)
        assert by_group, "fraction >= 0.3 over 24 requests never shared"
        heads = {}
        for g, members in by_group.items():
            hs = {tuple(r.prompt[:prefix_len]) for r in members}
            assert len(hs) == 1, f"group {g} does not share its prefix"
            heads[g] = hs.pop()
        # Distinct groups draw distinct prefixes (collision odds ~ vocab^-4).
        assert len(set(heads.values())) == len(heads)

    def test_full_fraction_covers_every_request(self):
        tr = generate(preset("shared-prefix", seed=7))
        assert all(r.prefix_group >= 0 for r in tr.requests)


# ---------------------------------------------------------------------------
# serialization
# ---------------------------------------------------------------------------

class TestTraceIO:
    @settings(max_examples=6, deadline=None)
    @given(name=st.sampled_from(PRESETS),
           seed=st.integers(min_value=0, max_value=2**20))
    def test_save_load_roundtrip(self, name, seed):
        # A directory per example: a function-scoped tmp_path would be
        # shared by every example hypothesis draws.
        tr = generate(preset(name, quick=True, seed=seed))
        with tempfile.TemporaryDirectory() as d:
            p = os.path.join(d, "trace.json")
            tr.save(p)
            tr2 = Trace.load(p)
        assert tr2.to_json() == tr.to_json()
        assert tr2.fingerprint() == tr.fingerprint()

    def test_version_gate(self):
        d = generate(preset("steady", quick=True)).to_dict()
        d["version"] = TRACE_VERSION + 1
        with pytest.raises(ValueError, match="version"):
            Trace.from_dict(d)

    def test_spec_roundtrip(self):
        spec = preset("eviction-pressure", quick=True, seed=5)
        assert WorkloadSpec.from_dict(spec.to_dict()) == spec

    def test_registry_covers_presets(self):
        for name in generator.WORKLOADS:
            assert preset(name).name == name
