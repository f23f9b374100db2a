"""graftlint: static analyzers for the distributed-correctness bug classes
this repo has actually hit.

Three halves, one Finding stream:

- :mod:`.jaxpr_audit` traces the real loss/train-step builders on the
  virtual-device CPU mesh and walks their closed jaxprs (collective axis
  binding, ppermute bijections, S-fold psum overcounts, dtype/weak-type
  hygiene, the chunked scan's checkpoint contract). Trace-only — no compile.
  :mod:`.shard_flow` ("graftprove") extends the walk with per-value
  sharded/replicated dataflow rules: redundant gathers of replicated
  values, scan state that is read-then-silently-dropped, and cross-branch
  collective-order consistency.
- :mod:`.config_space` ("graftprove") is the declarative feature model of
  the step-config axes: a constraint table, a solver enumerating the legal
  product (the lattice source for the traced sample), and a drift check
  probing every config through the real imperative refusal layers.
- :mod:`.repo_lint` is an AST pass over the package and the documents
  enforcing repo invariants (trace-time mutable globals, doc staleness in
  both directions, slow markers, the metrics schema).
- :mod:`.lock_flow` ("graftguard") is the concurrency half: guarded-by
  inference over every lock-owning class (unguarded writes, un-looped
  ``Condition.wait``, blocking calls under a lock, orphan threads), the
  static lock-acquisition graph with cycle detection, and the
  ``repo-lockwatch-gate`` proof that :mod:`..obs.lockwatch`'s runtime
  witness is dead in prod and every lock routes through it.

Run via ``python -m distributed_sigmoid_loss_tpu lint`` (exit 1 on findings,
``--json``, per-rule ``--disable``, ``--full-product`` for the
pairwise-covering sample, ``--baseline`` for ratchet mode), via the dryrun's
graftlint + graftprove tokens (__graft_entry__.py), and via
tests/test_analysis.py + tests/test_config_space.py so the gate is
self-enforcing on every future PR. Rule catalog + allowlist policy:
docs/ANALYSIS.md.
"""

from __future__ import annotations

from distributed_sigmoid_loss_tpu.analysis.findings import Finding  # noqa: F401
from distributed_sigmoid_loss_tpu.analysis.lock_flow import (  # noqa: F401
    LOCK_RULES,
    run_lock_flow,
)
from distributed_sigmoid_loss_tpu.analysis.repo_lint import (  # noqa: F401
    REPO_RULES,
    run_repo_lint,
)

__all__ = [
    "Finding",
    "ALL_RULES",
    "REPO_RULES",
    "LOCK_RULES",
    "JAXPR_RULES",
    "CONFIG_RULES",
    "META_RULES",
    "run_lint",
    "run_lock_flow",
    "load_lint_baseline",
    "apply_lint_baseline",
]

# jaxpr rule ids duplicated here (not imported) so listing rules — the CLI's
# --disable choices — never pays the jax import. The first seven live in
# jaxpr_audit, the last six in shard_flow; tests/test_analysis.py pins the
# literals against the source catalogs.
JAXPR_RULES = (
    "jaxpr-ppermute-bijection",
    "jaxpr-collective-axis",
    "jaxpr-double-psum",
    "jaxpr-f64",
    "jaxpr-weak-type",
    "jaxpr-chunk-checkpoint",
    "jaxpr-bf16-upcast",
    "jaxpr-redundant-gather",
    "jaxpr-state-drop",
    "jaxpr-collective-order",
    "jaxpr-ef-threaded",
    "jaxpr-codec-threaded",
    "jaxpr-gather-placement",
)

# config_space's declarative-vs-imperative cross-check (jax-light: the probe
# imports the builders but never traces).
CONFIG_RULES = ("config-space-drift",)

# Rules about the lint run itself: a --baseline entry that no longer fires.
META_RULES = ("lint-stale-suppression",)

ALL_RULES = REPO_RULES + LOCK_RULES + JAXPR_RULES + CONFIG_RULES + META_RULES


def run_lint(
    disabled=(),
    jaxpr: bool = True,
    n_devices: int | None = None,
    full_product: bool = False,
) -> list[Finding]:
    """Run the repo linter, the lock-flow analyzer, and (unless
    ``jaxpr=False``) the config-space drift check plus the jaxpr auditor
    over the sampled step-config product.

    ``disabled``: rule ids to drop from the result. ``n_devices``: virtual
    mesh size for the auditor (default: min(8, available)).
    ``full_product``: audit the pairwise-covering sample of the full legal
    config product instead of the tier-1 sample (reserved for the
    dryrun/driver — extra traces cost ~30 s).
    """
    disabled = set(disabled)
    findings = run_repo_lint(disabled=disabled)
    findings.extend(run_lock_flow(disabled=disabled))
    if jaxpr:
        # Imported lazily: the AST half must stay usable (and fast) in
        # processes that never initialize jax.
        from distributed_sigmoid_loss_tpu.analysis.config_space import (
            config_space_drift_findings,
        )
        from distributed_sigmoid_loss_tpu.analysis.jaxpr_audit import (
            audit_default_step_configs,
        )

        findings.extend(config_space_drift_findings())
        findings.extend(
            audit_default_step_configs(
                n_devices=n_devices, full_product=full_product
            )
        )
    return [f for f in findings if f.rule not in disabled]


def load_lint_baseline(path) -> list:
    """Parse a ``--baseline`` file: either a saved ``lint --json`` report
    (``{"findings": [...]}``) or a bare JSON list of finding dicts. Returns
    ``(rule, subject)`` keys — the stable identity findings are matched on
    (details may legitimately reword across versions)."""
    import json

    with open(path) as f:
        data = json.load(f)
    entries = data.get("findings", data) if isinstance(data, dict) else data
    keys = []
    for e in entries:
        if not isinstance(e, dict) or "rule" not in e or "subject" not in e:
            raise ValueError(
                f"baseline entry {e!r} needs 'rule' and 'subject' keys "
                "(write one with: lint --json > baseline.json)"
            )
        keys.append((e["rule"], e["subject"]))
    return keys


def apply_lint_baseline(findings: list, baseline_keys: list) -> list:
    """Ratchet mode: drop findings matching a baseline entry; every baseline
    entry that no longer fires becomes a ``lint-stale-suppression`` finding
    (the ratchet only tightens — fixed findings must leave the baseline)."""
    baseline = set(baseline_keys)
    kept = [f for f in findings if f.key() not in baseline]
    fired = {f.key() for f in findings}
    stale = [k for k in baseline_keys if k not in fired]
    for rule, subject in sorted(set(stale)):
        kept.append(
            Finding(
                "lint-stale-suppression",
                subject,
                f"baseline suppresses [{rule}] here but it no longer fires "
                "— remove the entry so the ratchet stays tight",
                location="lint --baseline",
            )
        )
    return kept
