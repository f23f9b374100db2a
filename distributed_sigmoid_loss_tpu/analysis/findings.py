"""The one Finding type every graftlint rule reports through.

Stdlib-only on purpose: the AST linter and the schema modules share it
without pulling jax into processes that never trace anything.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Finding"]


@dataclasses.dataclass(frozen=True)
class Finding:
    """One lint/audit finding.

    ``rule``: the rule id (stable, used by ``lint --disable``).
    ``subject``: what was audited — a step-config label for jaxpr rules, a
    ``path::name`` for repo rules.
    ``detail``: human-readable description of the violation and why it bites.
    ``location``: where to annotate — ``path:line`` for repo rules, a
    constraint/refusal source for config rules, a step-config label for
    jaxpr rules. Optional; empty when a rule has no better anchor than
    ``subject``.
    """

    rule: str
    subject: str
    detail: str
    location: str = ""

    def __str__(self) -> str:  # the `lint` CLI's text output line
        loc = f" ({self.location})" if self.location else ""
        return f"[{self.rule}] {self.subject}{loc}: {self.detail}"

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        # CI annotators key on rule_id; keep it alongside the short name so
        # `lint --json` consumers never parse the text line.
        d["rule_id"] = self.rule
        return d

    def key(self) -> tuple[str, str]:
        """Stable identity used by ``lint --baseline`` suppression."""
        return (self.rule, self.subject)
