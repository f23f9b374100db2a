"""graftprove self-enforcement: the declarative config-space solver.

The contract under test: the solver's legal product is the single source of
truth for which step configs exist; it must (a) contain every config the
auditor historically guarded (the fifteen legacy labels — the acceptance
pin), (b) agree exactly with the real imperative refusal layers (the drift
probe, falsified here by injection), and (c) feed the sampled lattice the
auditor/attribution consumers trace. Plus the Finding surface the
PR adds (rule_id + location in --json, baseline ratchet mode).

Standard tier: everything here is pure python over the feature model — the
probe builds loss closures but never traces, so no devices are needed.
"""

import itertools
import json

import pytest

import distributed_sigmoid_loss_tpu  # noqa: F401  (compat shims first)

from distributed_sigmoid_loss_tpu.analysis import (
    Finding,
    apply_lint_baseline,
    load_lint_baseline,
)
from distributed_sigmoid_loss_tpu.analysis import config_space as cs


# ---------------------------------------------------------------------------
# the solver: product, constraints, labels
# ---------------------------------------------------------------------------


def test_product_enumeration_and_constraint_pruning():
    raw = 1
    for values in cs.AXES.values():
        raw *= len(values)
    assert sum(1 for _ in cs.iter_product()) == raw
    legal = cs.enumerate_legal()
    assert 0 < len(legal) < raw
    # Every legal config satisfies every constraint; every pruned config
    # names at least one violated constraint (violations() is the witness).
    legal_set = set(legal)
    for cfg in itertools.islice(cs.iter_product(), 0, None, 7):
        if cfg in legal_set:
            assert cs.violations(cfg) == ()
        else:
            assert cs.violations(cfg), cfg
    # The default point (everything off) is the fused base config.
    assert cs.StepConfig() in legal_set


def test_legal_product_superset_of_legacy_fifteen():
    """The acceptance pin: the solver may only WIDEN coverage — all fifteen
    configs the hand-maintained list guarded are legal points, under their
    historical labels, and in the tier-1 sample."""
    legal = set(cs.enumerate_legal())
    assert len(cs.LEGACY_CONFIGS) == 15
    tier1 = cs.tier1_sample()
    for label, cfg in cs.LEGACY_CONFIGS.items():
        assert cfg in legal, label
        assert cs.label_of(cfg) == label
        assert tier1.get(label) == cfg
    # and the full-product sample contains the tier-1 sample in turn
    full = cs.full_product_sample()
    for label, cfg in tier1.items():
        assert full.get(label) == cfg
    assert set(full.values()) <= legal


def test_labels_are_unique_and_stable():
    full = cs.full_product_sample()
    for label, cfg in full.items():
        assert cs.label_of(cfg) == label
    # Non-legacy labels are the non-default axes in AXES order — stable
    # across runs (the per-label trace memo keys on it).
    ring_zero1 = cs.StepConfig(variant="ring", update_sharding="zero1")
    assert cs.label_of(ring_zero1) == "variant=ring+update_sharding=zero1"
    assert cs.label_of(cs.StepConfig(update_sharding="full")) == (
        "update_sharding=full"
    )


def test_full_product_sample_covers_all_legal_pairs():
    """The sample is a pairwise covering array over the traceable legal
    product: every (axis-pair, value-pair) that occurs in some traceable
    legal config occurs in the sample. Pairwise is the deliberate strength:
    the historical step bugs were two-axis interactions."""
    traceable = [c for c in cs.enumerate_legal() if cs._traceable(c)]
    sample = cs.full_product_sample().values()
    axes = [a for a in cs.AXES if a != "ema"]

    def pairs(cfg):
        vals = [getattr(cfg, a) for a in axes]
        return {
            (a1, vals[i], a2, vals[j])
            for i, a1 in enumerate(axes)
            for j, a2 in enumerate(axes)
            if i < j
        }

    wanted = set()
    for c in traceable:
        wanted |= pairs(c)
    covered = set()
    for c in sample:
        covered |= pairs(c)
    missing = wanted - covered
    assert not missing, sorted(missing)[:5]


def test_graftcodec_rows_registered():
    """graftcodec's axes land in the feature model: the learned compression
    value, the controller axis, and the three constraint rows that make the
    new corner refusable by the solver exactly where the code refuses it."""
    assert "learned" in cs.AXES["compression"]
    assert cs.AXES["controller"] == ("", "greedy", "budgeted")
    assert cs.is_legal(
        cs.StepConfig(compression="learned", error_feedback=True)
    )
    assert cs.is_legal(
        cs.StepConfig(
            compression="adaptive", error_feedback=True,
            controller="budgeted",
        )
    )
    no_ef = cs.violations(cs.StepConfig(compression="learned"))
    assert any(v.name == "learned-needs-error-feedback" for v in no_ef)
    with_pp = cs.violations(
        cs.StepConfig(compression="learned", error_feedback=True, pp=True)
    )
    assert any(v.name == "adaptive-excludes-pp" for v in with_pp)
    orphan = cs.violations(cs.StepConfig(controller="budgeted"))
    assert any(v.name == "controller-needs-adaptive" for v in orphan)
    assert any(
        v.name == "controller-needs-adaptive"
        for v in cs.violations(
            cs.StepConfig(compression="int8", controller="greedy")
        )
    )
    # The learned corners are in the traced tier-1 sample (the auditor's
    # jaxpr-codec-threaded rule needs a jaxpr to walk).
    tier1 = cs.tier1_sample()
    assert "compression=learned+error_feedback" in tier1
    assert "compression=learned+controller=budgeted+error_feedback" in tier1
    assert "compression=learned+error_feedback+update_sharding=full" in tier1


# ---------------------------------------------------------------------------
# the drift probe: solver vs the real imperative refusals
# ---------------------------------------------------------------------------


def test_no_drift_on_shipped_tree():
    findings = cs.config_space_drift_findings()
    assert findings == [], [str(f) for f in findings]


def test_drift_probe_falsified_by_injection():
    """Both drift directions must fire: a probe that REFUSES a legal config
    (imperative layer grew a refusal the model lacks) and one that ACCEPTS
    an illegal config (a constraint the code no longer enforces)."""
    legal = cs.StepConfig()
    illegal = cs.StepConfig(loss_impl="chunked", variant="ring")
    assert cs.violations(illegal)

    refuses_everything = lambda cfg: (False, "synthetic refusal")  # noqa: E731
    findings = cs.config_space_drift_findings(
        probe=refuses_everything, configs=[legal]
    )
    assert [f.rule for f in findings] == ["config-space-drift"]
    assert "synthetic refusal" in findings[0].detail

    accepts_everything = lambda cfg: (True, "")  # noqa: E731
    findings = cs.config_space_drift_findings(
        probe=accepts_everything, configs=[illegal]
    )
    assert [f.rule for f in findings] == ["config-space-drift"]
    # the finding points at the violated constraint's source location
    assert findings[0].location, findings[0]


def test_probe_agrees_with_solver_over_full_product():
    """The real three-layer probe, every legal config plus a slice of the
    illegal ones — the full cross-check `lint` runs, asserted directly."""
    legal = cs.enumerate_legal()
    for cfg in legal:
        ok, why = cs.probe_imperative(cfg)
        assert ok, f"{cs.label_of(cfg)}: {why}"
    rejected = [c for c in cs.iter_product() if not cs.is_legal(c)]
    for cfg in rejected[:: max(1, len(rejected) // 200)]:
        ok, _ = cs.probe_imperative(cfg)
        assert not ok, cs.label_of(cfg)


# ---------------------------------------------------------------------------
# Finding surface: rule_id + location, baseline ratchet
# ---------------------------------------------------------------------------


def test_finding_carries_rule_id_and_location():
    f = Finding("config-space-drift", "cfg", "detail", location="a.py::C")
    d = f.as_dict()
    assert d["rule_id"] == d["rule"] == "config-space-drift"
    assert d["location"] == "a.py::C"
    assert "(a.py::C)" in str(f)
    assert f.key() == ("config-space-drift", "cfg")
    bare = Finding("r", "s", "d")
    assert "()" not in str(bare)


def test_baseline_roundtrip_and_stale_suppression(tmp_path):
    findings = [
        Finding("repo-doc-stale", "cli.py::--x", "undocumented"),
        Finding("jaxpr-state-drop", "cfg", "dropped"),
    ]
    # a saved `lint --json` report and a bare list both load
    report = tmp_path / "baseline.json"
    report.write_text(json.dumps(
        {"findings": [f.as_dict() for f in findings]}
    ))
    keys = load_lint_baseline(report)
    assert keys == [f.key() for f in findings]
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps([f.as_dict() for f in findings]))
    assert load_lint_baseline(bare) == keys

    # both current findings suppressed -> empty
    assert apply_lint_baseline(list(findings), keys) == []
    # one finding fixed -> its entry is stale and must be reported
    out = apply_lint_baseline(findings[:1], keys)
    assert [f.rule for f in out] == ["lint-stale-suppression"]
    assert out[0].subject == "cfg"
    assert "jaxpr-state-drop" in out[0].detail
    # a new finding not in the baseline passes through untouched
    new = Finding("jaxpr-f64", "elsewhere", "fresh")
    out = apply_lint_baseline(findings + [new], keys)
    assert out == [new]


def test_baseline_rejects_malformed_entries(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps([{"rule": "only-rule"}]))
    with pytest.raises(ValueError, match="subject"):
        load_lint_baseline(bad)


def test_cli_lint_baseline_ratchet(capsys, monkeypatch, tmp_path):
    import distributed_sigmoid_loss_tpu.analysis as analysis
    from distributed_sigmoid_loss_tpu.cli import main

    current = [Finding("repo-doc-stale", "x", "drill finding")]
    monkeypatch.setattr(analysis, "run_lint", lambda **kw: list(current))
    baseline = tmp_path / "b.json"

    # exact baseline -> clean exit
    baseline.write_text(json.dumps([f.as_dict() for f in current]))
    assert main(["lint", "--no-jaxpr", "--baseline", str(baseline)]) == 0
    assert "0 finding(s)" in capsys.readouterr().err

    # stale entry -> lint-stale-suppression, exit 1
    baseline.write_text(json.dumps(
        [f.as_dict() for f in current]
        + [{"rule": "jaxpr-f64", "subject": "gone"}]
    ))
    assert main(["lint", "--no-jaxpr", "--baseline", str(baseline)]) == 1
    out, err = capsys.readouterr()
    assert "lint-stale-suppression" in out
    assert "1 finding(s)" in err

    # unreadable baseline is a usage error, not a crash
    assert main([
        "lint", "--no-jaxpr", "--baseline", str(tmp_path / "missing.json")
    ]) == 2
