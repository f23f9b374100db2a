"""The documents held to the code: graftlint's ``repo-doc-code`` rule
(analysis/repo_lint.py ``check_docs_against_code``), the reverse of
``repo-doc-stale``.

- falsified on a fixture document that names a missing file, shows a missing
  script, module and sub-command, and gives a flag (and an ``obs`` action) its
  sub-command lacks, beside a twin in which every such name is real;
- run over the tree, one case a document and one a ``cli.py`` sub-command, so
  a failure names the document or the parser that moved;
- and the two host-side benches run at the command lines README.md gives
  them: one valid record a line, and the working tree as it was (they append
  to no file).
"""

import functools
import json
import os
import subprocess
import sys

import pytest

from distributed_sigmoid_loss_tpu.analysis import repo_lint
from distributed_sigmoid_loss_tpu.analysis.bench_schema import validate_record

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUBCOMMANDS = (
    "train", "eval", "export", "tokenizer", "serve-bench", "data-bench",
    "obs", "lint",
)


def _read(name):
    with open(os.path.join(REPO, name), encoding="utf-8") as f:
        return f.read()


@functools.lru_cache(maxsize=1)
def _tree_findings():
    return repo_lint.check_docs_against_code()


# ---------------------------------------------------------------------------
# falsification: a known-bad fixture and its green twin
# ---------------------------------------------------------------------------

_BAD = """\
# A guide

The step lives in `train/train_step.py` and the old instrument in
`obs/ledgerr.py::append_record`; see also `gone_module.py` and
`docs/NOPE.md`, and the root record `GONE_LEDGER.jsonl`. A run writes
`DIR/telemetry.json` at 100 `pairs/s` (neither is a repo path).

```bash
python gone_script.py 288 10 b16        # a script that is gone
JAX_PLATFORMS=cpu python3 -m distributed_sigmoid_loss_tpu.obs.gone_mod /tmp/x
python -m distributed_sigmoid_loss_tpu frobnicate --steps 3
python -m distributed_sigmoid_loss_tpu train --tiny \\
    --steps 3 --no-such-flag 1 | tail -n 1
python -m pytest tests/ -q -n 6 --dist loadfile   # another tool's flags
```

Inline: `eval --quant int8 --bogus-eval-flag`, `obs ledger --top 3`, and a
pair of alternatives, `train --data-dir/--data-shardz`.
"""

_GOOD = """\
# A guide

The step lives in `train/train_step.py`, the exporter in
`obs/telemetry.py::environment_fingerprint`, the linter in `repo_lint.py`,
the kernels under `ops/`, the schema in `obs/metrics_schema.SERVE_STATS_FIELDS`,
the cells in `benchmark/configs/*.json` and the record in `PERF_LEDGER.jsonl`.

```bash
python chip_smoke.py --time-attention
python3 benchmark/run.py --workload b16-bs256
python -m distributed_sigmoid_loss_tpu.utils.profiling /tmp/trace_dir
python -m distributed_sigmoid_loss_tpu train --tiny \\
    --steps 3 --batch=16 | tail -n 1
python -m distributed_sigmoid_loss_tpu obs summarize runs/a --top 5
```

Inline: `eval --quant int8`, `obs diff runs/a runs/b`, `train --data-dir/--data-shards`.
"""


def test_doc_code_rule_trips_on_bad_fixture():
    findings = repo_lint.check_docs_against_code(documents={"GUIDE.md": _BAD})
    assert {f.rule for f in findings} == {"repo-doc-code"}
    assert sorted(f.subject for f in findings) == sorted([
        # (a) paths that are not in the tree
        "GUIDE.md::obs/ledgerr.py",
        "GUIDE.md::gone_module.py",
        "GUIDE.md::docs/NOPE.md",
        "GUIDE.md::GONE_LEDGER.jsonl",
        # (b) a script, a package module and a sub-command that do not exist
        "GUIDE.md::python gone_script.py",
        "GUIDE.md::python -m distributed_sigmoid_loss_tpu.obs.gone_mod",
        "GUIDE.md::frobnicate",
        # (c) flags and an action their sub-command lacks
        "GUIDE.md::train --no-such-flag",
        "GUIDE.md::eval --bogus-eval-flag",
        "GUIDE.md::obs ledger",
        "GUIDE.md::train --data-shardz",
    ])
    by_subject = {f.subject: f.detail for f in findings}
    assert "not in the tree" in by_subject["GUIDE.md::obs/ledgerr.py"]
    assert "no such sub-command" in by_subject["GUIDE.md::frobnicate"]
    assert "no such option" in by_subject["GUIDE.md::train --no-such-flag"]
    assert "summarize, diff" in by_subject["GUIDE.md::obs ledger"]


def test_doc_code_rule_green_on_good_fixture():
    assert repo_lint.check_docs_against_code(documents={"GUIDE.md": _GOOD}) == []


def test_doc_code_rule_reads_parsers_from_the_cli_source():
    """The flags come from cli.py's own add_parser / add_argument statements
    and from the helpers a parser is handed to: a fixture CLI in which `train`
    lost `--tiny` makes the good document stale."""
    cli = (
        "def _more(p):\n"
        '    p.add_argument("--steps")\n'
        '    p.add_argument("--batch")\n'
        "def main():\n"
        '    tr = sub.add_parser("train")\n'
        "    _more(tr)\n"
        '    ob = sub.add_parser("obs")\n'
        '    ob.add_argument("action", choices=["summarize"])\n'
        '    ob.add_argument("--top")\n'
    )
    subjects = [
        f.subject for f in repo_lint.check_docs_against_code(
            documents={"GUIDE.md": _GOOD}, cli_source=cli)
    ]
    # (`eval --quant int8` names no sub-command of this CLI: a backticked
    # span is an invocation only where its first word is one)
    assert subjects == [
        "GUIDE.md::train --tiny", "GUIDE.md::obs diff",
        "GUIDE.md::train --data-dir", "GUIDE.md::train --data-shards",
    ]


def test_doc_code_rule_ignores_the_scratch_copies_git_ignores(tmp_path):
    """A copy of the parent unpacked in `_parent/` (or chiprun_out/, a dot
    directory) must not answer for a file the change removed."""
    for d in ("_parent", "chiprun_out", ".cache", "pkg"):
        (tmp_path / d).mkdir()
        (tmp_path / d / "old_tool.py").write_text("")
    (tmp_path / "pkg" / "old_tool.py").unlink()
    (tmp_path / "pkg" / "kept.py").write_text("")
    findings = repo_lint.check_docs_against_code(
        documents={"GUIDE.md": "Run `old_tool.py`, then `pkg/kept.py`.\n"},
        repo_root=str(tmp_path),
    )
    assert [f.subject for f in findings] == ["GUIDE.md::old_tool.py"]


# ---------------------------------------------------------------------------
# the tree: one case a document, one a sub-command
# ---------------------------------------------------------------------------


def test_documents_and_subcommands_are_the_trees():
    assert set(repo_lint.DOCUMENTS) == {"README.md", "ARCHITECTURE.md"} | {
        "docs/" + f for f in os.listdir(os.path.join(REPO, "docs"))
        if f.endswith(".md")
    }
    parsers = repo_lint._subcommand_parsers(
        _read("distributed_sigmoid_loss_tpu/cli.py"))
    assert set(parsers) == set(SUBCOMMANDS)
    assert "--cpu-devices" in parsers["data-bench"]["flags"]  # cli.py's own
    assert "--image-hw" in parsers["data-bench"]["flags"]  # the helper's
    assert parsers["obs"]["choices"] == ["summarize", "diff"]


@pytest.mark.parametrize("doc", repo_lint.DOCUMENTS)
def test_document_names_only_what_exists(doc):
    mine = [f for f in _tree_findings() if f.subject.startswith(doc + "::")]
    assert mine == [], "\n".join(map(str, mine))
    # not vacuous: the document does name paths that were checked
    tree = repo_lint._Tree(repo_lint._tree_paths(REPO))
    named = [
        w for span in repo_lint._backticked(_read(doc)) for w in span.split()
        if repo_lint._named_repo_path(w, tree)
    ]
    assert len(named) >= 5, named


@pytest.mark.parametrize("cmd", SUBCOMMANDS)
def test_subcommand_is_documented_as_it_parses(cmd):
    mine = [
        f for f in _tree_findings()
        if f.subject.split("::", 1)[1].split()[0] == cmd
    ]
    assert mine == [], "\n".join(map(str, mine))
    # not vacuous: some document shows the sub-command being invoked
    parsers = repo_lint._subcommand_parsers(
        _read("distributed_sigmoid_loss_tpu/cli.py"))
    shown = [
        args for doc in repo_lint.DOCUMENTS
        for c, args in repo_lint.documented_invocations(_read(doc), parsers)
        if c == cmd
    ]
    assert shown, f"no document shows `{cmd}`"


def test_repo_lint_runs_the_rule():
    assert "repo-doc-code" in repo_lint.REPO_RULES
    assert "repo-bench-record" not in repo_lint.REPO_RULES
    assert "repo-ledger-emit" not in repo_lint.REPO_RULES
    assert [f for f in repo_lint.run_repo_lint() if f.rule == "repo-doc-code"] == []


# ---------------------------------------------------------------------------
# the host-side benches at README's command lines
# ---------------------------------------------------------------------------


def _tree_state():
    """What a stray output file would change: the checkout's paths (less what
    git ignores) and, where this is a git checkout, `git status`."""
    state = {"paths": sorted(repo_lint._tree_paths(REPO))}
    if os.path.isdir(os.path.join(REPO, ".git")):
        state["git"] = subprocess.run(
            ["git", "-C", REPO, "status", "--porcelain"],
            capture_output=True, text=True, timeout=60,
        ).stdout
    return state


@pytest.mark.parametrize("cmd, metrics", [
    ("serve-bench", {"serve_bench"}),
    ("data-bench", {"data_bench_stage", "data_bench_pipeline_pairs_per_sec"}),
])
def test_bench_at_its_readme_line_prints_records_and_writes_no_file(cmd, metrics):
    parsers = repo_lint._subcommand_parsers(
        _read("distributed_sigmoid_loss_tpu/cli.py"))
    lines = [
        args for c, args in repo_lint.documented_invocations(
            _read("README.md"), parsers)
        if c == cmd and "--metrics-port" not in args
    ]
    assert lines, f"README.md shows no `{cmd}` command line"
    before = _tree_state()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "distributed_sigmoid_loss_tpu", cmd, *lines[0]],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    records = [json.loads(ln) for ln in proc.stdout.splitlines()]
    assert {r["metric"] for r in records} == metrics
    for r in records:
        assert validate_record(r) == [], r
    assert "schema violation" not in proc.stderr
    assert _tree_state() == before
