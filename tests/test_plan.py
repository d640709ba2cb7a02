"""Planner properties: outer waterfilling, snapping, artifact (DESIGN §10).

The satellite property tests live here:

  * identical spectra/weights → the waterfilled allocation collapses to
    the uniform allocation, matching RateBudget's targets bit-for-bit;
  * two-group spectra → the analytic two-level waterfilling solution;
  * heterogeneous spectra → strictly lower weighted distortion than the
    even spread at a matched budget (the planner's reason to exist).
"""
import numpy as np
import pytest

from repro.core import RateBudget
from repro.core.theory import random_covariance
from repro.plan import (MatrixSensitivity, QuantPlan, allocation_distortion,
                        apply_constraints, build_plan, distortion_at_rate,
                        sensitivity_from_matrix, snap_bits, waterfill_bits)

from hypothesis import given, settings, strategies as st


def flat(name, v, n=32, a=16, w=1.0, **kw):
    """Layer with a flat spectrum: D(R) = v·4^{-R} exactly at every rate."""
    return MatrixSensitivity(name=name, out_features=a, in_features=n,
                             sigma_w2=1.0, lambdas=np.full(n, float(v)),
                             weight=w, **kw)


# ---------------------------------------------------------------------------
# Satellite: uniform collapse + two-group analytic solution
# ---------------------------------------------------------------------------


def test_identical_layers_collapse_to_uniform_bit_for_bit():
    """L identical layers: waterfilled == uniform == RateBudget targets,
    exactly (no bisection noise allowed in the degenerate case) — at the
    2-bit rung (the new lowest grid point) as well as mid-grid."""
    L = 6
    sigma, _ = random_covariance(24, condition=50.0, seed=3)
    sens = [sensitivity_from_matrix(f"L{i}/m", np.full((8, 24), 0.3), sigma)
            for i in range(L)]
    for B in (3.0, 2.0):
        bits = waterfill_bits(sens, B)
        assert bits.shape == (L,)
        assert np.all(bits == B)                  # bit-for-bit uniform
        rb = RateBudget(B, {s.name: s.n_params for s in sens})
        for s, b in zip(sens, bits):
            target = rb.next_target(s.name)
            assert b == target                    # matches RateBudget exactly
            rb.record(s.name, b)
        assert rb.realized_rate == B
        assert not rb.budget_overrun
    # the uniform 2.0 allocation snaps onto the real 2-bit serving rung
    snapped, overrun = snap_bits(sens, waterfill_bits(sens, 2.0),
                                 budget_bits_per_param=2.0)
    assert not overrun and np.all(snapped == 2.0)
    plan = build_plan(sens, 2.0)
    assert all(e.payload_bits == 2 for e in plan.entries)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), n_layers=st.integers(2, 8))
def test_property_identical_layers_uniform(seed, n_layers):
    rng = np.random.default_rng(seed)
    lam = np.abs(rng.standard_normal(16)) + 0.01
    B = float(rng.uniform(1.0, 6.0))
    sens = [MatrixSensitivity(name=f"L{i}/m", out_features=4 + i,
                              in_features=16, sigma_w2=0.7, lambdas=lam)
            for i in range(n_layers)]
    bits = waterfill_bits(sens, B)
    assert np.all(bits == B)


def test_two_group_matches_analytic_two_level_solution():
    """Flat two-group spectra: R_A − R_B = ½log₂(s_A/s_B), budget split by
    parameter mass — the closed-form two-level waterfilling solution.
    Budgets down to 2.25 put the cheap group's optimum near/below the new
    2-bit rung (the regime the int2 payload exists for)."""
    for (va, vb, na, nb, B) in [(16.0, 1.0, 2, 2, 3.0),
                                (64.0, 1.0, 1, 3, 4.0),
                                (9.0, 0.25, 3, 1, 2.5),
                                (16.0, 1.0, 2, 2, 2.25)]:
        sens = ([flat(f"a{i}", va) for i in range(na)]
                + [flat(f"b{i}", vb) for i in range(nb)])
        bits = waterfill_bits(sens, B)
        delta = 0.5 * np.log2(va / vb)
        # equal n_params per layer → masses are the layer counts
        r_a = B + nb / (na + nb) * delta
        r_b = B - na / (na + nb) * delta
        np.testing.assert_allclose(bits[:na], r_a, atol=1e-6)
        np.testing.assert_allclose(bits[na:], r_b, atol=1e-6)


def test_two_group_low_budget_snaps_to_int2_rung():
    """Satellite: 2-bit targets snap to the REAL 2-bit rung now — the
    cheap group lands on payload 2 (not ridden up to int3), the
    expensive group keeps its higher format, budget holds."""
    sens = ([flat(f"a{i}", 64.0) for i in range(2)]
            + [flat(f"b{i}", 1.0) for i in range(2)])
    B = 2.5
    cont = waterfill_bits(sens, B)
    assert cont[2] < 2.0 + 1e-9          # cheap group's optimum ≤ 2 bits
    snapped, overrun = snap_bits(sens, cont, budget_bits_per_param=B)
    assert not overrun
    by_payload = [float(b) for b in snapped]
    assert by_payload[2] == 2.0 and by_payload[3] == 2.0
    assert by_payload[0] >= 3.0
    plan = build_plan(sens, B)
    payloads = {e.name: e.payload_bits for e in plan.entries}
    assert payloads["b0"] == 2 and payloads["b1"] == 2
    n = np.array([s.n_params for s in sens], float)
    assert float(n @ snapped) / n.sum() <= B + 1e-9


# ---------------------------------------------------------------------------
# Acceptance: strict improvement over even spread at matched budget
# ---------------------------------------------------------------------------


def hetero_sens(n_layers=6, dim=24, seed=0):
    rng = np.random.default_rng(seed)
    decays = ["log-linear", "two-level", "flat", "heavy-tail"]
    out = []
    for i in range(n_layers):
        sigma, _ = random_covariance(dim, decay=decays[i % 4],
                                     condition=10.0 ** (1 + i % 4),
                                     seed=seed + i)
        w = rng.standard_normal((12, dim)) * (0.2 + 0.5 * (i % 3))
        out.append(sensitivity_from_matrix(f"L{i}/m", w, sigma))
    return out


def test_waterfill_strictly_beats_even_spread_predicted():
    sens = hetero_sens()
    for B in (2.0, 3.0, 4.0):
        bits = waterfill_bits(sens, B)
        n = np.array([s.n_params for s in sens], float)
        # matched budget (exactly B bits/param)
        assert float(n @ bits) / n.sum() == pytest.approx(B, abs=1e-9)
        d_wf = allocation_distortion(sens, bits)
        d_even = allocation_distortion(sens, [B] * len(sens))
        assert d_wf < d_even * (1 - 1e-6), (B, d_wf, d_even)


def test_waterfill_monotone_in_budget():
    sens = hetero_sens(seed=7)
    ds = [allocation_distortion(sens, waterfill_bits(sens, b))
          for b in (1.5, 2.5, 3.5, 4.5)]
    assert all(a > b for a, b in zip(ds, ds[1:]))


# ---------------------------------------------------------------------------
# Floors / ceilings / snapping
# ---------------------------------------------------------------------------


def test_floor_and_ceiling_respected():
    sens = hetero_sens(seed=1)
    apply_constraints(sens, floors={"L0/*": 4.0}, ceils={"L5/*": 3.0})
    bits = waterfill_bits(sens, 3.5)
    by = {s.name: b for s, b in zip(sens, bits)}
    assert by["L0/m"] >= 4.0 - 1e-12
    assert by["L5/m"] <= 3.0 + 1e-12
    n = np.array([s.n_params for s in sens], float)
    assert float(n @ bits) / n.sum() <= 3.5 + 1e-9


def test_infeasible_floors_raise():
    sens = [flat("a", 1.0, floor_bits=6.0), flat("b", 1.0, floor_bits=6.0)]
    with pytest.raises(ValueError, match="infeasible"):
        waterfill_bits(sens, 3.0)


def test_snap_respects_grid_budget_and_floors():
    sens = hetero_sens(seed=2)
    apply_constraints(sens, floors={"L0/*": 4.0})
    B = 3.0
    cont = waterfill_bits(sens, B)
    snapped, overrun = snap_bits(sens, cont, budget_bits_per_param=B)
    assert not overrun
    assert set(np.unique(snapped)) <= {2.0, 3.0, 4.0, 8.0}
    by = {s.name: b for s, b in zip(sens, snapped)}
    assert by["L0/m"] >= 4.0
    n = np.array([s.n_params for s in sens], float)
    assert float(n @ snapped) / n.sum() <= B + 1e-9
    # snapped allocation is never better than the continuous optimum but
    # at least as good as the even spread on this heterogeneous set
    assert allocation_distortion(sens, snapped) \
        >= allocation_distortion(sens, cont) * (1 - 1e-9)
    assert allocation_distortion(sens, snapped) \
        <= allocation_distortion(sens, [B] * len(sens)) * (1 + 1e-9)


def test_snap_downgrades_when_grid_minimum_overspends():
    """Low-rate layers forced up to the grid minimum must be paid for by
    downgrading rich layers, not by silently exceeding the budget."""
    sens = [flat("cheap0", 1e-4), flat("cheap1", 1e-4), flat("rich", 4e3)]
    cont = waterfill_bits(sens, 3.0)
    assert cont[0] < 1.0 and cont[2] > 5.0       # strongly skewed optimum
    snapped, overrun = snap_bits(sens, cont, budget_bits_per_param=3.0)
    assert not overrun
    n = np.array([s.n_params for s in sens], float)
    assert float(n @ snapped) / n.sum() <= 3.0 + 1e-9


def test_snap_true_overrun_is_flagged():
    sens = [flat("a", 1.0, floor_bits=4.0), flat("b", 1.0, floor_bits=2.0)]
    snapped, overrun = snap_bits(sens, np.array([4.0, 2.0]),
                                 budget_bits_per_param=2.0)
    assert overrun                                # 4+2 over a 2.0 budget
    plan = build_plan(sens, 3.0)                  # feasible budget: plan OK
    assert isinstance(plan, QuantPlan)


# ---------------------------------------------------------------------------
# Artifact: round trip, diff, histograms
# ---------------------------------------------------------------------------


def test_plan_artifact_roundtrip_and_diff(tmp_path):
    sens = hetero_sens(seed=4)
    plan = build_plan(sens, 3.0, weighting="uniform",
                      provenance={"arch": "synthetic", "seed": 4})
    path = str(tmp_path / "plan.json")
    plan.save(path)
    re = QuantPlan.load(path)
    assert re == plan
    assert re.diff(plan) == []
    # a second build at another budget diffs cleanly
    plan2 = build_plan(sens, 2.0, weighting="uniform")
    delta = plan.diff(plan2)
    assert delta and all(l.startswith("~") for l in delta)
    # schema gate: future versions are rejected, not misread
    import json
    d = json.loads(plan.to_json())
    d["schema_version"] = 99
    with pytest.raises(ValueError, match="schema_version"):
        QuantPlan.from_dict(d)


def test_plan_histograms_and_serving_formats():
    sens = hetero_sens(seed=5)
    plan = build_plan(sens, 3.0, weighting="uniform")
    per_layer = plan.per_layer_bits()
    assert set(per_layer) == set(range(6))
    hist = plan.payload_histogram()
    assert sum(hist.values()) == len(plan.entries)
    assert set(hist) <= {2, 3, 4, 8}
    assert plan.planned_bits_per_param <= 3.0 + 1e-9


def test_pred_distortion_matches_curve():
    sens = hetero_sens(seed=6)
    plan = build_plan(sens, 3.0, weighting="uniform")
    by_name = {s.name: s for s in sens}
    for e in plan:
        assert e.pred_distortion == pytest.approx(
            distortion_at_rate(by_name[e.name], e.snapped_bits), rel=1e-9)
