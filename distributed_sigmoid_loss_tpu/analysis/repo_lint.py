"""graftlint's AST half: repo invariants that are statically checkable.

Every rule here encodes a bug class this repo actually hit (or a contract a
prior PR established), enforced at lint time instead of re-litigated in
review:

- ``repo-mutable-global``: module-level mutable state that can influence
  traced behavior must be allowlisted WITH a rationale naming its traced-choice
  recorder (a step traced before the mutation keeps the other behavior while
  its record claims otherwise).
- ``repo-doc-stale``: every CLI flag and LossConfig field must appear in
  README.md or docs/ (a flag nobody can discover is a flag nobody A/Bs).
- ``repo-slow-marker``: the registered multi-minute suites must carry the
  module-level ``slow`` marker (protects tier-1's time limit: the driver runs
  ``-n 6 --dist loadfile -m 'not slow'`` under a 1470 s timeout, 777 s at PR 45).
- ``repo-doc-code``: the reverse of repo-doc-stale. Every repo path a document
  names in backticks exists, every ``python ...`` command it shows names a
  script, module or sub-command that exists, and every ``--flag`` it gives
  for a sub-command is in that sub-command's parser (a recipe for a file that
  is gone outlives the file by twenty PRs otherwise).
- ``repo-metrics-schema``: every train metrics-line / serve ``stats()`` /
  health-event field literal in the emitting modules must be registered in
  ``obs/metrics_schema.py`` (a metric added in one step builder but not
  declared is invisible to every downstream parser until it breaks one).
- ``repo-chaos-gate``: every fault-injection point in serve/ must be a
  ``maybe_inject("<point>")`` call whose point is a string constant
  registered in ``serve/siege.py CHAOS_POINTS`` with a non-empty rationale,
  ``maybe_inject`` itself must check the ``chaos_enabled()`` gate, and
  ``chaos_enabled`` must key on the ``DSL_CHAOS`` env hook — so injection
  code is provably dead in production paths, and the registry stays an
  honest inventory (stale rows fail too).

All checks take explicit source/path inputs so tests can falsify each rule on
a known-bad fixture; the defaults audit the real repo.
"""

from __future__ import annotations

import ast
import fnmatch
import os
import re
import shlex

from distributed_sigmoid_loss_tpu.analysis.findings import Finding

__all__ = [
    "REPO_RULES",
    "run_repo_lint",
    "check_mutable_globals",
    "check_doc_staleness",
    "check_slow_markers",
    "check_docs_against_code",
    "check_metrics_schema",
    "check_chaos_gate",
    "MUTABLE_GLOBAL_ALLOWLIST",
    "SLOW_REQUIRED_TEST_MODULES",
    "METRICS_SCHEMA_FILES",
    "DOCUMENTS",
]

REPO_RULES = (
    "repo-mutable-global",
    "repo-doc-stale",
    "repo-slow-marker",
    "repo-doc-code",
    "repo-metrics-schema",
    "repo-chaos-gate",
)

_PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REPO_ROOT = os.path.dirname(_PACKAGE_DIR)

# Module-level mutable globals the repo accepts, each with the rationale the
# rule's docstring demands. Policy (docs/ANALYSIS.md): state that selects a
# TRACED behavior is allowlistable only when a trace-time recorder exists and
# the record emitters cross-check it; host-side caches must never be read
# inside traced code.
MUTABLE_GLOBAL_ALLOWLIST = {
    "ops/pallas_sigmoid_loss.py::_TRACED_LOSS_KERNELS": (
        "trace-time recorder for the streaming-loss-kernel dispatch "
        "(streaming / streaming_int8 / xla fallback); chip_smoke.py and "
        "tests/test_pallas_loss.py read it so use_pallas can never be "
        "claimed while every block fell back (append-only at trace time; "
        "cleared only by the test-isolation reset)"
    ),
    "data/native_loader.py::_lib": (
        "host-side ctypes build/load cache for the C++ dataloader; never "
        "read inside traced code (data feeding happens on the host)"
    ),
    "data/native_decode.py::_lib": (
        "host-side ctypes build/load cache for the libjpeg engine; never "
        "read inside traced code"
    ),
    "data/native_decode.py::_lib_failed": (
        "host-side build-failure latch paired with _lib; never read inside "
        "traced code"
    ),
    "serve/siege.py::_INJECTORS": (
        "host-side armed-fault registry for the chaos harness; never read "
        "inside traced code (injection happens on worker/host threads), "
        "mutated only by install_fault/clear_faults under _INJECT_LOCK, and "
        "dead in production: maybe_inject is gated on DSL_CHAOS "
        "(statically enforced by repo-chaos-gate)"
    ),
    "analysis/jaxpr_audit.py::_STEP_CONFIG_CACHE": (
        "host-side per-label memo of the deterministic step-config traces "
        "(auditor + obs/attribution share one sampled product, and the "
        "full-product pass reuses the tier-1 labels); never read inside "
        "traced code — it CONTAINS closed jaxprs, which are inert data"
    ),
}

# The suites whose full-module runtime is multi-minute on one core (measured;
# see CHANGES.md PR 1-3): each must carry a module-level
# `pytestmark = pytest.mark.slow` so tier-1 (`-m 'not slow'`) never collects
# them.
SLOW_REQUIRED_TEST_MODULES = (
    "test_cli.py",
    "test_grad_compression.py",
    "test_train_step.py",
    "test_pp_towers.py",
    "test_zero1.py",
    "test_long_context.py",
    "test_quant_train_convergence.py",
)

_MUTATING_METHODS = {
    "add", "append", "extend", "update", "clear", "pop", "popitem",
    "remove", "discard", "insert", "setdefault", "appendleft",
}

_MUTABLE_CTORS = {"set", "dict", "list", "deque", "defaultdict", "OrderedDict"}


def _module_level_names(tree: ast.Module) -> set[str]:
    names = set()
    for node in tree.body:
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
            targets = [node.target]
        for t in targets:
            if isinstance(t, ast.Name):
                names.add(t.id)
    return names


def _local_bindings(fn: ast.AST) -> set[str]:
    """Names bound locally in a function (params + assignments), EXCLUDING
    names it declares ``global``."""
    bound, globals_ = set(), set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Global):
            globals_.update(node.names)
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    bound.add(t.id)
        elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
            if isinstance(node.target, ast.Name):
                bound.add(node.target.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for a in (
                node.args.args + node.args.posonlyargs + node.args.kwonlyargs
            ):
                bound.add(a.arg)
    return bound - globals_


def _mutated_module_globals(tree: ast.Module) -> dict[str, int]:
    """name -> line of the first detected mutation of a module-level name."""
    module_names = _module_level_names(tree)
    mutable_containers = set()
    for node in tree.body:
        target = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and isinstance(
            node.targets[0], ast.Name
        ):
            target = node.targets[0].id
        elif isinstance(node, ast.AnnAssign) and isinstance(
            node.target, ast.Name
        ):
            target = node.target.id
        if target is None or node.value is None:
            continue
        v = node.value
        is_container = isinstance(v, (ast.List, ast.Dict, ast.Set)) or (
            isinstance(v, ast.Call)
            and isinstance(v.func, ast.Name)
            and v.func.id in _MUTABLE_CTORS
        )
        if is_container:
            mutable_containers.add(target)

    mutated: dict[str, int] = {}

    def note(name: str, line: int) -> None:
        mutated.setdefault(name, line)

    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        declared_global = {
            n for node in ast.walk(fn) if isinstance(node, ast.Global)
            for n in node.names
        }
        local = _local_bindings(fn)
        for node in ast.walk(fn):
            # `global N` + assignment: rebinding a module global from a function.
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = (
                    node.targets if isinstance(node, ast.Assign) else [node.target]
                )
                for t in targets:
                    if isinstance(t, ast.Name) and t.id in declared_global:
                        note(t.id, node.lineno)
                    # container[k] = v on a module-level container
                    if (
                        isinstance(t, ast.Subscript)
                        and isinstance(t.value, ast.Name)
                        and t.value.id in mutable_containers
                        and t.value.id not in local
                    ):
                        note(t.value.id, node.lineno)
            # container.add/append/... on a module-level container
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _MUTATING_METHODS
                and isinstance(node.func.value, ast.Name)
            ):
                name = node.func.value.id
                if name in module_names and name in mutable_containers and (
                    name not in local
                ):
                    note(name, node.lineno)
    return mutated


def _iter_package_sources(package_dir: str):
    for dirpath, dirnames, filenames in os.walk(package_dir):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, package_dir)
            with open(path, encoding="utf-8") as f:
                yield rel, f.read()


def check_mutable_globals(
    sources=None, allowlist=None,
) -> list[Finding]:
    """repo-mutable-global: unallowlisted mutated module-level state.

    ``sources``: ``{relpath: source}`` (default: every package module).
    """
    if sources is None:
        sources = dict(_iter_package_sources(_PACKAGE_DIR))
    allowlist = MUTABLE_GLOBAL_ALLOWLIST if allowlist is None else allowlist
    findings = []
    seen_keys = set()
    for rel, src in sources.items():
        rel = rel.replace(os.sep, "/")
        tree = ast.parse(src)
        for name, line in sorted(_mutated_module_globals(tree).items()):
            key = f"{rel}::{name}"
            seen_keys.add(key)
            if key not in allowlist:
                findings.append(Finding(
                    "repo-mutable-global",
                    key,
                    f"module-level {name!r} is mutated (line {line}) — "
                    "trace-time mutable global state; a step traced before "
                    "the mutation silently keeps the other behavior while "
                    "records claim otherwise. Either remove it or allowlist "
                    "it in analysis/repo_lint.py with a rationale naming its "
                    "traced-choice recorder",
                ))
    for key in sorted(set(allowlist) - seen_keys):
        findings.append(Finding(
            "repo-mutable-global",
            key,
            "stale allowlist entry: no such mutated module global exists "
            "anymore — drop it so the allowlist stays an honest inventory",
        ))
    return findings


def _argparse_flags(tree: ast.Module) -> dict[str, int]:
    """'--flag' -> lineno for every OPTIONAL add_argument in the module."""
    flags: dict[str, int] = {}
    for node in ast.walk(tree):
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "add_argument"
            and node.args
        ):
            continue
        first = node.args[0]
        if (
            isinstance(first, ast.Constant)
            and isinstance(first.value, str)
            and first.value.startswith("--")
        ):
            flags.setdefault(first.value, node.lineno)
    return flags


def check_doc_staleness(
    cli_source: str | None = None,
    config_source: str | None = None,
    docs_text: str | None = None,
) -> list[Finding]:
    """repo-doc-stale: CLI flags and LossConfig fields must appear in
    README.md or docs/*.md."""
    if cli_source is None:
        with open(
            os.path.join(_PACKAGE_DIR, "cli.py"), encoding="utf-8"
        ) as f:
            cli_source = f.read()
    if config_source is None:
        with open(
            os.path.join(_PACKAGE_DIR, "utils", "config.py"), encoding="utf-8"
        ) as f:
            config_source = f.read()
    if docs_text is None:
        chunks = []
        readme = os.path.join(_REPO_ROOT, "README.md")
        if os.path.exists(readme):
            with open(readme, encoding="utf-8") as f:
                chunks.append(f.read())
        docs_dir = os.path.join(_REPO_ROOT, "docs")
        if os.path.isdir(docs_dir):
            for fn in sorted(os.listdir(docs_dir)):
                if fn.endswith(".md"):
                    with open(
                        os.path.join(docs_dir, fn), encoding="utf-8"
                    ) as f:
                        chunks.append(f.read())
        docs_text = "\n".join(chunks)

    findings = []
    cli_tree = ast.parse(cli_source)
    for flag, line in sorted(_argparse_flags(cli_tree).items()):
        # Positionals (e.g. `export out`) are visible in --help usage strings;
        # only true --flags are held to the doc rule.
        if flag not in docs_text:
            findings.append(Finding(
                "repo-doc-stale",
                f"cli.py::{flag}",
                f"CLI flag {flag} (line {line}) appears in no README.md "
                "or docs/*.md — undocumented surface goes un-A/B'd and "
                "rots; add a line where the subcommand is documented",
            ))
    cfg_tree = ast.parse(config_source)
    for node in ast.walk(cfg_tree):
        if isinstance(node, ast.ClassDef) and node.name == "LossConfig":
            for stmt in node.body:
                if isinstance(stmt, ast.AnnAssign) and isinstance(
                    stmt.target, ast.Name
                ):
                    field = stmt.target.id
                    if field not in docs_text:
                        findings.append(Finding(
                            "repo-doc-stale",
                            f"LossConfig.{field}",
                            f"LossConfig field {field!r} appears in no "
                            "README.md or docs/*.md",
                        ))
    return findings


def check_slow_markers(
    sources=None, required=None,
) -> list[Finding]:
    """repo-slow-marker: registered multi-minute suites carry the module-level
    slow pytestmark (the structural guard of tier-1's 1470 s time limit)."""
    required = SLOW_REQUIRED_TEST_MODULES if required is None else required
    if sources is None:
        sources = {}
        tests_dir = os.path.join(_REPO_ROOT, "tests")
        for fn in required:
            path = os.path.join(tests_dir, fn)
            if os.path.exists(path):
                with open(path, encoding="utf-8") as f:
                    sources[fn] = f.read()
            else:
                sources[fn] = None
    findings = []
    for fn in required:
        src = sources.get(fn)
        if src is None:
            findings.append(Finding(
                "repo-slow-marker", f"tests/{fn}",
                "registered as slow-required but the file does not exist — "
                "update SLOW_REQUIRED_TEST_MODULES",
            ))
            continue
        tree = ast.parse(src)
        if not _has_module_slow_mark(tree):
            findings.append(Finding(
                "repo-slow-marker", f"tests/{fn}",
                "multi-minute suite without a module-level `pytestmark = "
                "pytest.mark.slow` — it would land inside tier-1's "
                "`-m 'not slow'` run and its 1470 s time limit",
            ))
    return findings


def _has_module_slow_mark(tree: ast.Module) -> bool:
    def is_slow_mark(node) -> bool:
        # pytest.mark.slow, possibly wrapped: pytest.mark.slow / mark.slow
        return (
            isinstance(node, ast.Attribute)
            and node.attr == "slow"
            and isinstance(node.value, ast.Attribute)
            and node.value.attr == "mark"
        )

    for node in tree.body:
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == "pytestmark"
            for t in node.targets
        ):
            v = node.value
            elems = v.elts if isinstance(v, (ast.List, ast.Tuple)) else [v]
            if any(is_slow_mark(e) for e in elems):
                return True
            # pytest.mark.skipif(...) etc: calls wrapping a mark — check func
            if any(
                isinstance(e, ast.Call) and is_slow_mark(e.func) for e in elems
            ):
                return True
    return False


# The documents repo-doc-code holds to the tree (repo-root-relative paths).
DOCUMENTS = (
    "README.md",
    "ARCHITECTURE.md",
    "docs/ANALYSIS.md",
    "docs/MIGRATION.md",
    "docs/OBSERVABILITY.md",
    "docs/SERVING.md",
)

_PACKAGE_NAME = os.path.basename(_PACKAGE_DIR)
_SOURCE_EXTS = (".py", ".md", ".cc")
_DATA_EXTS = (".json", ".jsonl", ".toml")
_FENCED = re.compile(r"^```[^\n]*\n(.*?)^```", re.S | re.M)
_PATH_TOKEN = re.compile(r"^[A-Za-z0-9_*\-][A-Za-z0-9_.*\-]*(/[A-Za-z0-9_.*\-]+)*/?$")
_LINE_SUFFIX = re.compile(r":\d+(-\d+)?$")
_SHELL_BREAKS = {"|", "||", "&&", ";", ">", ">>", "2>&1", "&"}


def _backticked(text: str) -> list[str]:
    """A document's inline code spans, fenced blocks aside. A span may wrap
    over a line end but not over a paragraph's, so that one stray backtick
    cannot flip the pairing for the rest of the document."""
    spans = []
    for paragraph in re.split(r"\n\s*\n", _FENCED.sub("", text)):
        spans.extend(" ".join(m.split()) for m in re.findall(r"`([^`]+)`", paragraph))
    return spans


def _tree_paths(repo_root: str) -> set[str]:
    """Every file and directory of the checkout, '/'-separated and
    root-relative. Hidden directories, ``__pycache__`` and the scratch
    directories git ignores (a leading underscore, ``chiprun_out``) are not
    the tree: a copy of the parent commit unpacked there must not answer for
    a file the change removed."""
    paths: set[str] = set()
    for dirpath, dirnames, filenames in os.walk(repo_root):
        dirnames[:] = [
            d for d in dirnames
            if not d.startswith((".", "_")) and d != "chiprun_out"
        ]
        rel = os.path.relpath(dirpath, repo_root).replace(os.sep, "/")
        prefix = "" if rel == "." else rel + "/"
        paths.update(prefix + d for d in dirnames)
        paths.update(prefix + f for f in filenames)
    return paths


class _Tree:
    """The checkout as repo-doc-code sees it: a named path resolves when it is
    the tail of some path in the tree (documents name ``ops/quant.py`` and
    ``fleet/leases.py`` from inside the package), globs included."""

    def __init__(self, paths: set[str]):
        self.tails: set[str] = set()
        self.dir_names: set[str] = set()
        for p in paths:
            parts = p.split("/")
            self.tails.update("/".join(parts[i:]) for i in range(len(parts)))
            self.dir_names.update(parts[:-1])

    def has(self, token: str) -> bool:
        token = token.rstrip("/")
        if "*" in token:
            return any(fnmatch.fnmatchcase(t, token) for t in self.tails)
        return token in self.tails

    def resolves(self, token: str) -> bool:
        """``token`` as written, as a module (``ops/quant`` for
        ``ops/quant.py``), or less a dotted attribute
        (``ops/quant.quantize_int8``)."""
        cand = token
        while True:
            if self.has(cand) or self.has(cand + ".py"):
                return True
            head, dot, _ = cand.rpartition(".")
            if not dot or "/" in cand[len(head):]:
                return False
            cand = head


def _named_repo_path(span_word: str, tree: _Tree) -> str | None:
    """The repo path a backticked word names, or None when it names none: a
    word of path characters that ends in a source extension, or whose first
    segment is a directory of the tree (``DIR/telemetry.json``, ``pairs/s`` and
    ``/tmp/x`` are not repo paths), or a capitalised data file at the root
    (``PERF_LEDGER.jsonl``; ``telemetry.json`` is a run's output)."""
    word = span_word.strip("()[],;").rstrip(".:")
    word = _LINE_SUFFIX.sub("", word.split("::")[0])
    if not _PATH_TOKEN.match(word):
        return None
    if "/" in word.rstrip("/"):
        return word if word.split("/")[0] in tree.dir_names else None
    if word.endswith("/"):
        return None
    if word.endswith(_SOURCE_EXTS):
        return word
    if word.endswith(_DATA_EXTS) and word[0].isupper():
        return word
    return None


def _subcommand_parsers(cli_source: str) -> dict[str, dict]:
    """sub-command -> {"flags": set of option strings, "choices": the
    choices of its first positional or None}, read from cli.py's
    ``X = sub.add_parser("name")`` / ``X.add_argument(...)`` statements and
    from the helpers a parser is handed to (``_add_obs_args(ob)``,
    ``add_data_bench_args(db)``)."""
    tree = ast.parse(cli_source)
    imported: dict[str, str] = {}
    local_defs: dict[str, ast.FunctionDef] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module and (
            node.module.startswith(_PACKAGE_NAME + ".")
        ):
            for alias in node.names:
                imported[alias.asname or alias.name] = node.module
        elif isinstance(node, ast.FunctionDef):
            local_defs.setdefault(node.name, node)

    def helper_def(name: str) -> ast.FunctionDef | None:
        if name in local_defs:
            return local_defs[name]
        if name in imported:
            rel = imported[name].split(".", 1)[1].replace(".", os.sep) + ".py"
            path = os.path.join(_PACKAGE_DIR, rel)
            if os.path.exists(path):
                with open(path, encoding="utf-8") as f:
                    for node in ast.parse(f.read()).body:
                        if isinstance(node, ast.FunctionDef) and node.name == name:
                            return node
        return None

    def add_argument_calls(scope: ast.AST, var: str):
        for node in ast.walk(scope):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == var
            ):
                yield node

    def take(call: ast.Call, spec: dict) -> None:
        names = [
            a.value for a in call.args
            if isinstance(a, ast.Constant) and isinstance(a.value, str)
        ]
        if names and names[0].startswith("-"):
            spec["flags"].update(names)
        elif names and "choices" not in spec:  # the first positional
            spec["choices"] = None
            for kw in call.keywords:
                if kw.arg == "choices" and isinstance(kw.value, (ast.List, ast.Tuple)):
                    spec["choices"] = [
                        e.value for e in kw.value.elts if isinstance(e, ast.Constant)
                    ]

    parsers: dict[str, dict] = {}
    spec_of: dict[str, dict] = {}  # parser variable -> its sub-command's spec
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and isinstance(node.value, ast.Call)
            and isinstance(node.value.func, ast.Attribute)
            and node.value.func.attr == "add_parser"
            and node.value.args
            and isinstance(node.value.args[0], ast.Constant)
        ):
            spec_of[node.targets[0].id] = parsers[node.value.args[0].value] = {
                "flags": {"-h", "--help"}
            }
    for var, spec in spec_of.items():
        for call in add_argument_calls(tree, var):
            take(call, spec)
    for node in ast.walk(tree):  # a parser handed to a helper: `helper(var)`
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and len(node.args) == 1
            and isinstance(node.args[0], ast.Name)
            and node.args[0].id in spec_of
        ):
            fn = helper_def(node.func.id)
            if fn is not None and fn.args.args:
                for call in add_argument_calls(fn, fn.args.args[0].arg):
                    take(call, spec_of[node.args[0].id])
    for spec in parsers.values():
        spec.setdefault("choices", None)
    return parsers


def _command_words(text: str) -> list[list[str]]:
    """Every command a document shows, as words: the lines of its fenced
    blocks (continuations joined, comments cut) and its backticked spans."""
    commands = []
    for block in _FENCED.findall(text):
        commands.extend(block.replace("\\\n", " ").splitlines())
    commands.extend(_backticked(text))
    out = []
    for line in commands:
        try:
            words = shlex.split(line, comments=True)
        except ValueError:
            words = line.split("#")[0].split()
        if words:
            out.append(words)
    return out


def _after_python(words: list[str]) -> list[str] | None:
    """The words that follow the interpreter in a command line, or None."""
    for i, w in enumerate(words):
        if os.path.basename(w) in ("python", "python3"):
            return words[i + 1:]
    return None


def documented_invocations(text: str, subcommands) -> list[tuple[str, list[str]]]:
    """(sub-command, the words after it) for every CLI invocation a document
    shows: ``python -m <package> CMD ...`` and a backticked ``CMD ...``. A
    name that is not a sub-command comes back too, when the package's module
    was invoked with it."""
    found = []
    for words in _command_words(text):
        rest = _after_python(words)
        if rest is not None:
            rest = rest[2:] if rest[:2] == ["-m", _PACKAGE_NAME] else None
        elif words[0] in subcommands and len(words) > 1:
            rest = words
        if rest and not rest[0].startswith("-"):
            args = []
            for w in rest[1:]:
                if w in _SHELL_BREAKS:
                    break
                args.append(w)
            found.append((rest[0], args))
    return found


def check_docs_against_code(
    documents: dict[str, str] | None = None,
    cli_source: str | None = None,
    repo_root: str | None = None,
) -> list[Finding]:
    """repo-doc-code: what a document names exists. ``documents``:
    ``{name: text}`` (default: :data:`DOCUMENTS` read from the tree).

    Three halves, each a way a document outlives the code it describes: (a) a
    repo path in backticks that is not in the tree; (b) a ``python ...``
    command whose script, package module or sub-command does not exist; (c) a
    ``--flag`` (or, where the first positional has choices, an action) given
    for a sub-command whose parser has none such."""
    repo_root = _REPO_ROOT if repo_root is None else repo_root
    if documents is None:
        documents = {}
        for name in DOCUMENTS:
            with open(os.path.join(repo_root, name), encoding="utf-8") as f:
                documents[name] = f.read()
    if cli_source is None:
        with open(os.path.join(_PACKAGE_DIR, "cli.py"), encoding="utf-8") as f:
            cli_source = f.read()
    tree = _Tree(_tree_paths(repo_root))
    parsers = _subcommand_parsers(cli_source)
    findings = []

    def report(doc: str, what: str, why: str) -> None:
        finding = Finding("repo-doc-code", f"{doc}::{what}", why)
        if finding not in findings:
            findings.append(finding)

    for doc, text in documents.items():
        # (a) repo paths in backticks
        for span in _backticked(text):
            for word in span.split():
                path = _named_repo_path(word, tree)
                if path is not None and not tree.resolves(path):
                    report(doc, path,
                           f"names `{path}`, which is not in the tree — "
                           "reword the passage or drop it with the file")
        # (b) python commands: scripts and package modules
        for words in _command_words(text):
            rest = _after_python(words) or [""]
            if rest[0] == "-m" and len(rest) > 1:
                module = rest[1]
                if module.startswith(_PACKAGE_NAME + ".") and not tree.resolves(
                    module.replace(".", "/")
                ):
                    report(doc, f"python -m {module}",
                           f"shows `python -m {module}`: no such module")
            elif rest[0].endswith(".py") and not tree.resolves(rest[0]):
                report(doc, f"python {rest[0]}",
                       f"shows `python {rest[0]}`: no such script")
        # (b) sub-commands, (c) their flags and actions
        for cmd, args in documented_invocations(text, parsers):
            if cmd not in parsers:
                report(doc, cmd,
                       f"shows `python -m {_PACKAGE_NAME} {cmd}`: cli.py has "
                       f"no such sub-command (it has {', '.join(sorted(parsers))})")
                continue
            spec = parsers[cmd]
            if spec["choices"] and args and not args[0].startswith("-") and (
                args[0] not in spec["choices"]
            ):
                report(doc, f"{cmd} {args[0]}",
                       f"gives `{cmd} {args[0]}`: `{cmd}` takes one of "
                       f"{', '.join(spec['choices'])}")
            for word in args:
                # `--data-dir/--data-shards`: alternatives, each a flag
                for part in word.split("/"):
                    flag = part.split("=")[0].rstrip(",.;:)")
                    if flag.startswith("--") and len(flag) > 2 and (
                        flag not in spec["flags"]
                    ):
                        report(doc, f"{cmd} {flag}",
                               f"gives {flag} for `{cmd}`, whose parser has "
                               "no such option")
    return findings

_METRIC_DICT_NAMES = {"metrics", "line", "snap"}

# The modules whose metric-field literals repo-metrics-schema audits, and the
# registry (obs/metrics_schema.py) each validates against. Package-relative
# paths; a module emitting a NEW record stream registers itself here.
METRICS_SCHEMA_FILES = {
    "train/train_step.py": "train",
    "train/compressed_step.py": "train",
    "cli.py": "train",
    "serve/service.py": "serve",
    "serve/admission.py": "serve",
    "serve/fleet/leases.py": "serve",
    "serve/fleet/router.py": "serve",
    "serve/fleet/waves.py": "serve",
    "obs/health.py": "health",
}


def _metric_literals(tree: ast.Module) -> list[tuple[str, int]]:
    """(field, lineno) for every metric-field string literal in a module:
    dict literals bound to the conventional record names (``metrics`` /
    ``line`` / ``snap``), subscript-assigns onto them, dict literals passed
    to ``.log(step, {...})`` / ``.write({...})``, and the dict a function
    named ``record`` returns (the HealthEvent convention). Dynamic keys
    (f-strings like ``eval/{k}``) are invisible to AST and covered by the
    registered prefixes at emit time instead."""
    out: list[tuple[str, int]] = []

    def take(d: ast.Dict, line: int) -> None:
        for k in d.keys:
            if isinstance(k, ast.Constant) and isinstance(k.value, str):
                out.append((k.value, line))

    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if (
                    isinstance(t, ast.Name)
                    and t.id in _METRIC_DICT_NAMES
                    and isinstance(node.value, ast.Dict)
                ):
                    take(node.value, node.lineno)
                if (
                    isinstance(t, ast.Subscript)
                    and isinstance(t.value, ast.Name)
                    and t.value.id in _METRIC_DICT_NAMES
                    and isinstance(t.slice, ast.Constant)
                    and isinstance(t.slice.value, str)
                ):
                    out.append((t.slice.value, node.lineno))
        elif isinstance(node, ast.Call) and isinstance(
            node.func, ast.Attribute
        ):
            if (
                node.func.attr == "log"
                and len(node.args) >= 2
                and isinstance(node.args[1], ast.Dict)
            ):
                take(node.args[1], node.lineno)
            elif (
                node.func.attr == "write"
                and node.args
                and isinstance(node.args[0], ast.Dict)
            ):
                take(node.args[0], node.lineno)
        elif isinstance(node, ast.FunctionDef) and node.name == "record":
            for stmt in ast.walk(node):
                if isinstance(stmt, ast.Return) and isinstance(
                    stmt.value, ast.Dict
                ):
                    take(stmt.value, stmt.lineno)
    return out


def check_metrics_schema(sources=None, files=None) -> list[Finding]:
    """repo-metrics-schema: metric-field literals in the emitting modules are
    all registered in obs/metrics_schema.py (train lines / serve stats /
    health events — the repo-bench-record discipline for the other two
    record streams)."""
    from distributed_sigmoid_loss_tpu.obs.metrics_schema import (
        HEALTH_EVENT_FIELDS,
        SERVE_STATS_FIELDS,
        TRAIN_METRICS_FIELDS,
        TRAIN_METRICS_PREFIXES,
    )

    schemas = {
        "train": (TRAIN_METRICS_FIELDS, TRAIN_METRICS_PREFIXES),
        "serve": (SERVE_STATS_FIELDS, ()),
        "health": (HEALTH_EVENT_FIELDS, ()),
    }
    files = METRICS_SCHEMA_FILES if files is None else files
    if sources is None:
        sources = {}
        for rel in files:
            path = os.path.join(_PACKAGE_DIR, rel.replace("/", os.sep))
            with open(path, encoding="utf-8") as f:
                sources[rel] = f.read()
    findings = []
    for rel, kind in files.items():
        src = sources.get(rel)
        if src is None:
            continue
        fields, prefixes = schemas[kind]
        for field_name, line in _metric_literals(ast.parse(src)):
            if field_name in fields:
                continue
            if any(field_name.startswith(p) for p in prefixes):
                continue
            findings.append(Finding(
                "repo-metrics-schema",
                f"{rel}::{field_name}",
                f"metric field {field_name!r} (line {line}) is not "
                f"registered in obs/metrics_schema.py ({kind} schema) — "
                "undeclared fields drift per emit path and are invisible "
                "to downstream parsers; register it (and document it in "
                "docs/OBSERVABILITY.md if it encodes a new signal)",
            ))
    return findings


def _chaos_registry(tree: ast.Module) -> dict[str, str] | None:
    """CHAOS_POINTS {point: rationale} from siege's module body (string
    constants only), or None when the dict is missing entirely."""
    for node in tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and node.targets[0].id == "CHAOS_POINTS"
            and isinstance(node.value, ast.Dict)
        ):
            out = {}
            for k, v in zip(node.value.keys, node.value.values):
                if not (isinstance(k, ast.Constant) and isinstance(k.value, str)):
                    continue
                rationale = ""
                if isinstance(v, ast.Constant) and isinstance(v.value, str):
                    rationale = v.value
                elif isinstance(v, ast.JoinedStr):
                    rationale = "<dynamic>"
                out[k.value] = rationale
            return out
    return None


def _maybe_inject_calls(tree: ast.Module) -> list[tuple[str | None, int]]:
    """(point-or-None, lineno) for every maybe_inject(...) call; None marks
    a non-constant point argument (unauditable — itself a finding)."""
    calls = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = f.id if isinstance(f, ast.Name) else (
            f.attr if isinstance(f, ast.Attribute) else None
        )
        if name != "maybe_inject":
            continue
        point = None
        if node.args and isinstance(node.args[0], ast.Constant) and isinstance(
            node.args[0].value, str
        ):
            point = node.args[0].value
        calls.append((point, node.lineno))
    return calls


def _calls_name(fn: ast.AST, target: str) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Name) and f.id == target:
                return True
            if isinstance(f, ast.Attribute) and f.attr == target:
                return True
    return False


def check_chaos_gate(
    siege_source: str | None = None, serve_sources=None,
) -> list[Finding]:
    """repo-chaos-gate: fault injection provably dead in production paths.

    Four statically-checkable halves: (a) ``maybe_inject`` must check the
    ``chaos_enabled()`` gate before any fault can fire, and ``chaos_enabled``
    must key on the ``DSL_CHAOS`` env hook; (b) every point in
    ``CHAOS_POINTS`` carries a non-empty rationale; (c) every
    ``maybe_inject(...)`` call site in serve/ names a registered point with
    a STRING CONSTANT (a computed point is unauditable); (d) no registry row
    is stale — a registered point nobody calls is a drill that silently
    stopped existing.
    """
    serve_dir = os.path.join(_PACKAGE_DIR, "serve")
    if siege_source is None:
        with open(
            os.path.join(serve_dir, "siege.py"), encoding="utf-8"
        ) as f:
            siege_source = f.read()
    if serve_sources is None:
        serve_sources = {
            f"serve/{rel}": src
            for rel, src in _iter_package_sources(serve_dir)
        }
    findings = []
    siege_tree = ast.parse(siege_source)

    # (a) the gate itself.
    fns = {
        node.name: node
        for node in ast.walk(siege_tree)
        if isinstance(node, ast.FunctionDef)
    }
    if "maybe_inject" not in fns:
        findings.append(Finding(
            "repo-chaos-gate", "serve/siege.py::maybe_inject",
            "no maybe_inject function found — the chaos harness has no "
            "gated injection entry point",
        ))
    elif not _calls_name(fns["maybe_inject"], "chaos_enabled"):
        findings.append(Finding(
            "repo-chaos-gate", "serve/siege.py::maybe_inject",
            "maybe_inject does not check chaos_enabled() — an armed fault "
            "would fire in production without the DSL_CHAOS hook; gate it",
        ))
    if "chaos_enabled" not in fns:
        findings.append(Finding(
            "repo-chaos-gate", "serve/siege.py::chaos_enabled",
            "no chaos_enabled function found — nothing defines the "
            "DSL_CHAOS gate",
        ))
    else:
        reads_hook = any(
            isinstance(n, ast.Constant) and n.value == "DSL_CHAOS"
            for n in ast.walk(fns["chaos_enabled"])
        )
        if not reads_hook:
            findings.append(Finding(
                "repo-chaos-gate", "serve/siege.py::chaos_enabled",
                "chaos_enabled does not reference the 'DSL_CHAOS' env hook "
                "— the documented production off-switch is not what the "
                "gate actually checks",
            ))

    # (b) the registry + rationales.
    registry = _chaos_registry(siege_tree)
    if registry is None:
        findings.append(Finding(
            "repo-chaos-gate", "serve/siege.py::CHAOS_POINTS",
            "no CHAOS_POINTS dict found — injection points have no "
            "registered inventory",
        ))
        registry = {}
    for point, rationale in sorted(registry.items()):
        if not rationale.strip():
            findings.append(Finding(
                "repo-chaos-gate", f"serve/siege.py::{point}",
                f"chaos point {point!r} has no rationale — the registry "
                "must say which failure mode the drill exists for",
            ))

    # (c) every call site names a registered constant point.
    called: set[str] = set()
    for rel in sorted(serve_sources):
        for point, line in _maybe_inject_calls(ast.parse(serve_sources[rel])):
            if rel.endswith("siege.py"):
                continue  # the definition module, not an injection site
            if point is None:
                findings.append(Finding(
                    "repo-chaos-gate", f"{rel}::maybe_inject",
                    f"maybe_inject call at line {line} passes a computed "
                    "point — unauditable; injection points must be string "
                    "constants registered in CHAOS_POINTS",
                ))
                continue
            called.add(point)
            if point not in registry:
                findings.append(Finding(
                    "repo-chaos-gate", f"{rel}::{point}",
                    f"maybe_inject({point!r}) at line {line} is not "
                    "registered in serve/siege.py CHAOS_POINTS — register "
                    "it with a rationale (ungated/undocumented injection "
                    "points are exactly what this rule exists to prevent)",
                ))

    # (d) stale registry rows.
    for point in sorted(set(registry) - called):
        findings.append(Finding(
            "repo-chaos-gate", f"serve/siege.py::{point}",
            f"chaos point {point!r} is registered but no serve/ module "
            "calls maybe_inject with it — stale inventory row; drop it or "
            "wire the drill back in",
        ))
    return findings


def run_repo_lint(disabled=()) -> list[Finding]:
    """Run every repo rule against the real tree."""
    checks = {
        "repo-mutable-global": check_mutable_globals,
        "repo-doc-stale": check_doc_staleness,
        "repo-slow-marker": check_slow_markers,
        "repo-doc-code": check_docs_against_code,
        "repo-metrics-schema": check_metrics_schema,
        "repo-chaos-gate": check_chaos_gate,
    }
    findings: list[Finding] = []
    for rule, fn in checks.items():
        if rule not in disabled:
            findings.extend(fn())
    return findings
