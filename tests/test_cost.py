"""repro.analysis.cost: the static cost model and its regression gate.

Three layers: pure gate semantics on synthetic reports (thresholds at
X−ε/X+ε, missing cells, refusal transitions, baseline round-trip), one
real lowered cell end-to-end (schema + roofline + static impact), and the
committed-baseline contract (`BENCH_cost_baseline.json` covers the smoke
matrix and a synthetic fused-env regression fails loudly through the CLI).
"""
import copy
import json
import os
import subprocess
import sys

import pytest

from repro.analysis import audit
from repro.analysis.cost import (DEFAULT_THRESHOLDS, GATED_METRICS,
                                 SMOKE_BACKENDS, check, cost_cell,
                                 cost_train_cell, family_of, plan, run,
                                 summary_table, threshold_for)
from repro.core.registry import registered
from repro.sustainability.impact import StaticImpact

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")
BASELINE = os.path.join(os.path.dirname(__file__), os.pardir,
                        "BENCH_cost_baseline.json")


def _fake_report(**overrides):
    """A minimal two-cell report for pure check() tests."""
    row = {
        "id": "CartPole-v1", "backend": "pallas", "batch": 4,
        "family": "classic", "status": "ok", "env_steps_per_program": 4,
        "flops_per_step": 1000.0, "bytes_per_step": 4000.0,
        "peak_live_bytes": 2000.0,
    }
    refused = {"id": "Pendulum-v1", "backend": "pallas", "batch": 4,
               "family": "classic", "status": "refused",
               "refusal": "ValueError", "refusal_msg": "no fused support"}
    report = {"meta": {"platform": "cpu"}, "rows": [dict(row), dict(refused)]}
    for k, v in overrides.items():
        report["rows"][0][k] = v
    return report


# -- gate semantics (pure functions, no lowering) -----------------------------

def test_self_diff_is_clean():
    base = _fake_report()
    problems, notes = check(_fake_report(), base)
    assert problems == [] and notes == []


@pytest.mark.parametrize("metric", GATED_METRICS)
def test_threshold_pass_at_x_minus_eps_fail_at_x_plus_eps(metric):
    base = _fake_report()
    thr = threshold_for("classic")
    b = base["rows"][0][metric]
    ok = check(_fake_report(**{metric: b * (1 + thr - 1e-3)}), base)
    assert ok[0] == []
    problems, _ = check(_fake_report(**{metric: b * (1 + thr + 1e-3)}), base)
    assert len(problems) == 1
    # loud failure: named cell + metric + signed delta
    assert "CartPole-v1×pallas" in problems[0]
    assert metric in problems[0] and "+" in problems[0]


def test_improvement_beyond_threshold_is_a_note_not_a_problem():
    base = _fake_report()
    problems, notes = check(_fake_report(flops_per_step=500.0), base)
    assert problems == []
    assert any("improved" in n and "regen" in n for n in notes)


def test_missing_cell_and_new_refusal_are_problems():
    base = _fake_report()
    gone = _fake_report()
    gone["rows"] = gone["rows"][1:]
    problems, _ = check(gone, base)
    assert any("missing" in p for p in problems)
    now_refused = _fake_report()
    now_refused["rows"][0] = {
        "id": "CartPole-v1", "backend": "pallas", "batch": 4,
        "family": "classic", "status": "refused",
        "refusal": "RuntimeError", "refusal_msg": "boom"}
    problems, _ = check(now_refused, base)
    assert any("now refused" in p and "RuntimeError" in p for p in problems)


def test_batch_change_is_a_problem_not_a_silent_rescale():
    problems, _ = check(_fake_report(batch=8), _fake_report())
    assert any("batch changed" in p for p in problems)


def test_new_cell_and_newly_hosted_are_notes():
    base = _fake_report()
    grown = _fake_report()
    grown["rows"].append({"id": "Maze-v0", "backend": "vmap", "batch": 4,
                          "family": "grid", "status": "ok",
                          "env_steps_per_program": 4, "flops_per_step": 1.0,
                          "bytes_per_step": 1.0, "peak_live_bytes": 1.0})
    grown["rows"][1] = {**grown["rows"][1], "status": "ok",
                        "env_steps_per_program": 4, "flops_per_step": 1.0,
                        "bytes_per_step": 1.0, "peak_live_bytes": 1.0}
    problems, notes = check(grown, base)
    assert problems == []
    assert any("new cell" in n for n in notes)
    assert any("newly hosted" in n for n in notes)


def test_per_family_thresholds_cover_every_registry_family():
    for env_id in registered():
        fam = family_of(env_id)
        assert fam in DEFAULT_THRESHOLDS, (env_id, fam)
    assert family_of("dqn/CartPole-v1", audit.TRAIN_BACKEND) == "train"
    assert threshold_for("arcade") > 0 and threshold_for("nonsense") > 0


def test_plan_covers_the_audit_matrix():
    """Registry-completeness: the full cost plan is exactly the audit plan
    — every hosted audit cell has a cost row."""
    assert set(plan()) == set(audit.plan())
    smoke = plan(backends=SMOKE_BACKENDS)
    assert {i for i, _ in smoke} == set(registered())


# -- one real cell end-to-end -------------------------------------------------

def test_cost_cell_schema_and_physics():
    row = cost_cell("CartPole-v1", "vmap", batch=4)
    assert row["status"] == "ok"
    assert row["family"] == "classic"
    assert row["env_steps_per_program"] == 4
    assert row["flops"] == pytest.approx(row["flops_per_step"] * 4)
    assert row["flops_per_step"] > 0 and row["bytes_per_step"] > 0
    assert row["peak_live_bytes"] > 0
    assert row["arithmetic_intensity"] == pytest.approx(
        row["flops_per_step"] / row["bytes_per_step"])
    rl = row["roofline"]
    assert rl["dominant"] in ("compute", "memory", "collective")
    assert rl["bound_s"] == pytest.approx(
        max(rl["compute_s"], rl["memory_s"], rl["collective_s"]))
    imp = row["static_impact"]
    assert imp["joules_per_mstep"] == pytest.approx(
        rl["bound_s"] * imp["watts"] * 1e6)
    assert imp["co2_g_per_mstep"] > 0
    json.dumps(row)  # machine-readable end to end


def _unfused_id():
    from repro.core.env import supports_fused_step
    from repro.core.registry import make
    return next(i for i in sorted(registered())
                if not supports_fused_step(make(i)))


def test_cost_cell_refusal_is_named():
    row = cost_cell(_unfused_id(), "pallas", batch=4)
    assert row["status"] == "refused"
    assert row["refusal"] in audit.EXPECTED_REFUSALS


def test_cost_train_cell_unknown_id_refuses_by_name():
    row = cost_train_cell("dqn/NoSuchEnv-v9")
    assert row["status"] == "refused" and row["refusal"] == "KeyError"


def test_baseline_regen_round_trip():
    """run → dump → load → check against itself: clean, no notes."""
    report = run(ids=["CartPole-v1"], backends=("vmap",), train=False)
    loaded = json.loads(json.dumps(report))
    problems, notes = check(report, loaded)
    assert problems == [] and notes == []
    assert summary_table(report)  # renders without blowing up


# -- the committed baseline contract ------------------------------------------

def test_committed_baseline_covers_the_smoke_matrix():
    with open(BASELINE) as f:
        base = json.load(f)
    cells = {(r["id"], r["backend"]) for r in base["rows"]}
    for key in plan(backends=SMOKE_BACKENDS):
        assert key in cells, f"baseline is missing {key}; run make cost-baseline"
    from repro.train.fused import GOLDEN_TRAIN_IDS
    for gid in GOLDEN_TRAIN_IDS:
        assert (gid, audit.TRAIN_BACKEND) in cells
    hosted = [r for r in base["rows"] if r["status"] == "ok"]
    for r in hosted:
        for metric in GATED_METRICS:
            assert r.get(metric, 0) > 0, (r["id"], r["backend"], metric)


def test_synthetic_fused_regression_fails_loudly_through_the_cli(tmp_path):
    """The acceptance criterion, executed: inflate a fused env's baseline
    expectation downward (equivalently, the fresh compile regressed above
    threshold) and the CLI must exit nonzero naming cell, metric, delta."""
    fresh = run(ids=["CartPole-v1"], backends=("pallas",), train=False)
    tampered = copy.deepcopy(fresh)
    for r in tampered["rows"]:
        r["flops_per_step"] /= 1.5  # fresh compile now +50% over baseline
    path = tmp_path / "tampered_baseline.json"
    path.write_text(json.dumps(tampered))
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis.cost", "--ids", "CartPole-v1",
         "--backends", "pallas", "--no-train", "--batch", "4",
         "--check", str(path)],
        env=env, capture_output=True, text=True, cwd=os.path.dirname(SRC))
    assert out.returncode == 1, out.stdout + out.stderr
    assert "COST REGRESSION" in out.stdout
    assert "CartPole-v1×pallas" in out.stdout
    assert "flops_per_step" in out.stdout and "+50" in out.stdout
    # and the untampered baseline passes the same sweep
    path.write_text(json.dumps(fresh))
    ok = subprocess.run(
        [sys.executable, "-m", "repro.analysis.cost", "--ids", "CartPole-v1",
         "--backends", "pallas", "--no-train", "--batch", "4",
         "--check", str(path)],
        env=env, capture_output=True, text=True, cwd=os.path.dirname(SRC))
    assert ok.returncode == 0, ok.stdout + ok.stderr


# -- table2 static rows -------------------------------------------------------

def test_table2_static_rows_prefer_pallas_and_cover_all_ids():
    from benchmarks.table2_carbon import static_rows
    with open(BASELINE) as f:
        base = json.load(f)
    rows = static_rows(base)
    for env_id in registered():
        assert env_id in rows, f"no static table2 row for {env_id}"
        assert rows[env_id]["joules_per_mstep"] > 0
        assert rows[env_id]["co2_g_per_mstep"] > 0
    # pallas preferred where hosted, named fallback where refused
    assert rows["CartPole-v1"]["backend"] == "pallas"
    assert rows[_unfused_id()]["backend"] == "vmap"
    assert rows["dqn/CartPole-v1"]["family"] == "train"


def test_static_impact_accounting():
    imp = StaticImpact(seconds_per_step=1e-6, watts=200.0)
    assert imp.joules_per_step == pytest.approx(2e-4)
    assert imp.joules_per_mstep == pytest.approx(200.0)
    assert imp.kwh_per_mstep == pytest.approx(200.0 / 3.6e6)
    assert imp.co2_g_per_mstep == pytest.approx(
        200.0 / 3.6e6 * 0.475 * 1e3)
    json.dumps(imp.report())


def test_roofline_peaks_are_keyed_by_device_kind():
    """The static model prices a v5e, by name; a device kind with no
    published peaks raises instead of borrowing another chip's."""
    from benchmarks.roofline import PEAKS, V5E, peaks
    from repro.analysis.cost import TARGET_DEVICE_KIND

    assert TARGET_DEVICE_KIND == V5E == "TPU v5 lite"
    assert peaks(V5E) is PEAKS[V5E]
    with pytest.raises(KeyError, match="TPU v4"):
        peaks("TPU v4")
