"""The documents name files and switches that exist.

One case per document. A case reads its document and holds it to two things:

(a) every token that looks like a path to a `.py`, `.md`, `.json`, `.yml` or
    `.ini` file resolves: a token with a directory part from the repository
    root, from the document's own directory or from the package directory
    (prose cites modules by their import path, `parallel/mesh.py`); a bare
    file name when some file of the tree carries it. `<placeholder>` and `*`
    match anything, `{a,b}` is expanded. Files that exist only at run time
    are told apart by `NOT_IN_THE_TREE`, a reason each.
(b) every `SHEEPRL_TPU_*` variable it names is read somewhere under
    `sheeprl_tpu/`, `tools/`, `benchmark/` or in `chip_smoke.py`, by its whole
    name or through an f-string on its prefix (`SHEEPRL_TPU_PALLAS_{kind}`).
"""

import fnmatch
import itertools
import os
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

DOCUMENTS = sorted(
    ["README.md", ".claude/skills/verify/SKILL.md", ".github/workflows/tests.yml"]
    + [str(p.relative_to(REPO)) for p in (REPO / "howto").glob("*.md")]
)

# by file name: what a document may name although the tree does not hold it
NOT_IN_THE_TREE = {
    "args.json": "every main writes its flags into its run directory",
    "eval_args.json": "an --eval_only run writes its flags beside the checkpoint's",
    "ckpt_*.args.json": "the flags a checkpoint was saved under, beside the checkpoint",
    "decisions.json": "the measured-decision store, beside the compile cache",
    "scan_unroll.json": "the decision store's name before PR 11, still migrated on load",
    "serve_ladder.json": "the serve tier's batch-ladder decisions, in its log directory",
    "serve_quant.json": "the serve tier's int8 decisions, in its log directory",
    "bf16_upcast_audit.json": "an artifact the workflow's sheepcheck step uploads",
    "sheepopt_proposals.json": "an artifact the workflow's sheepopt step uploads",
    "budget.json": "the budget ledger's name before it became analysis/budget/; the tools map it",
    "sota.py": "the guide's example name for an algorithm the reader is about to add",
}

# directories of this checkout that git would not commit
_NOT_THE_TREE = {".git", ".bench_checkout", "scratch", "chiprun_out", "benchmark_out", "__pycache__", "jax_compile_cache"}

PATH_TOKEN = re.compile(r"[A-Za-z0-9_.@\-/<>*{},]*[A-Za-z0-9_>*}]\.(?:py|md|json|yml|ini)\b")
VARIABLE = re.compile(r"SHEEPRL_TPU_[A-Z_0-9]+(?:\{[A-Z_0-9,]+\})?")


def _braces_expanded(token: str) -> list[str]:
    parts = re.split(r"\{([^{}]*)\}", token)
    choices = [part.split(",") if i % 2 else [part] for i, part in enumerate(parts)]
    return ["".join(pick) for pick in itertools.product(*choices)]


def _named(pattern: re.Pattern, text: str) -> set[str]:
    return {name for found in pattern.findall(text) for name in _braces_expanded(found)}


class Tree:
    """What the documents are held to: the tree's file names and the variables its code reads."""

    def __init__(self):
        self.file_names = set()
        for _, dirs, files in os.walk(REPO):
            dirs[:] = [d for d in dirs if d not in _NOT_THE_TREE]
            self.file_names.update(files)
        sources = [REPO / "chip_smoke.py"]
        for base in ("sheeprl_tpu", "tools", "benchmark"):
            sources += (REPO / base).rglob("*.py")
        code = "\n".join(path.read_text() for path in sources)
        self.variables = set(re.findall(r"SHEEPRL_TPU_[A-Z_0-9]+", code))
        self.variable_prefixes = tuple(set(re.findall(r"(SHEEPRL_TPU_[A-Z_0-9]+_)\{", code)))

    def holds(self, token: str, document: str) -> bool:
        pattern = re.sub(r"<[^<>]*>", "*", token)
        name = os.path.basename(pattern)
        if any(fnmatch.fnmatchcase(name, runtime) for runtime in NOT_IN_THE_TREE):
            return True
        if "/" not in pattern:
            return bool(fnmatch.filter(self.file_names, pattern))
        bases = (REPO, (REPO / document).parent, REPO / "sheeprl_tpu")
        return any(any(base.glob(pattern.lstrip("/"))) for base in bases)

    def reads(self, variable: str) -> bool:
        through_prefix = variable.startswith(self.variable_prefixes) and variable not in self.variable_prefixes
        return variable in self.variables or through_prefix


@pytest.fixture(scope="module")
def tree():
    return Tree()


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_what_exists(document, tree):
    text = (REPO / document).read_text()
    missing = sorted(t for t in _named(PATH_TOKEN, text) if not tree.holds(t, document))
    assert not missing, f"{document} names files that are not in the tree: {missing}"
    unread = sorted(v for v in _named(VARIABLE, text) if not tree.reads(v))
    assert not unread, f"{document} names variables that nothing reads: {unread}"
