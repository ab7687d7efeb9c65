"""A document's commands run files that exist.

Every command a document gives (a line of a fenced block, or an inline code
span, that starts with ``python``, ``python3``, ``pytest`` or ``chiprun``,
environment assignments before it allowed) may name only files of the tree:
each repo-relative ``*.py`` path in it, and each ``-m`` module of this
package. ``tasks.py`` is held the same way through its ``run(...)`` calls,
and every ``tests/...py`` it names exists. History in running prose may name
a deleted tool as deleted; a command may not."""

import ast
import os
import re
import shlex

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = [
    "README.md",
    "docs/data-preproc.md",
    "docs/decoder-lm.md",
    "docs/observability.md",
    "docs/parallelism.md",
    "docs/performance.md",
    "docs/robustness.md",
    "docs/serving.md",
    "docs/static-analysis.md",
    "docs/training-examples.md",
    ".claude/skills/verify/SKILL.md",
    "tasks.py",
]

_COMMAND = re.compile(
    r"^(?:[A-Z][A-Z0-9_]*=\S*\s+)*"
    r"(?:chiprun\s+(?:--(?:chips|timeout)\s+\S+\s+)*--\s+)?(?:python3?|pytest)\s"
)


def _words(command: str) -> list:
    command = command.split(" #")[0]
    try:
        return shlex.split(command)
    except ValueError:  # an unbalanced quote in prose: the plain words will do
        return command.split()


def named_in_command(command: str) -> list:
    """The repo-relative ``*.py`` paths and this package's ``-m`` modules (as
    paths) that a command line names; placeholders, globs and absolute paths
    are not the tree's."""
    words = _words(command)
    found = []
    for before, word in zip([""] + words, words):
        word = word.strip("`'\"(),;")
        path = word.split("::")[0]
        if before == "-m" and path.split(".")[0] == "perceiver_io_tpu":
            found.append(path.replace(".", "/") + ".py")
        elif path.endswith(".py") and not re.search(r"[<>*$]|^/|^~", path):
            found.append(path)
    return found


def commands_of_markdown(text: str) -> list:
    fenced, prose, inside, pending = [], [], False, ""
    for line in text.splitlines():
        if line.lstrip().startswith("```"):
            inside = not inside
            continue
        if not inside:
            prose.append(line)
            continue
        pending += line.strip()
        if pending.endswith("\\"):  # a continued command is one command
            pending = pending[:-1] + " "
            continue
        fenced.append(pending)
        pending = ""
    spans = re.findall(r"`([^`]+)`", " ".join(prose))
    return [c for c in fenced + [" ".join(s.split()) for s in spans] if _COMMAND.match(c)]


def named_in_tasks(source: str) -> list:
    """String arguments of ``run(...)`` calls that are ``*.py`` files, and
    every ``tests/...py`` string the module holds."""
    found = []
    for node in ast.walk(ast.parse(source)):
        strings = []
        if isinstance(node, ast.Call) and getattr(node.func, "id", None) == "run":
            strings = [a.value for a in node.args if isinstance(a, ast.Constant)]
        elif isinstance(node, ast.Constant) and str(node.value).startswith("tests/"):
            strings = [node.value]
        found += [s for s in strings if isinstance(s, str) and s.endswith(".py")]
    return found


def named_files(document: str) -> list:
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    if document.endswith(".py"):
        return named_in_tasks(text)
    return [p for c in commands_of_markdown(text) for p in named_in_command(c)]


def test_the_extraction_reads_commands_and_leaves_prose():
    text = (
        "Prose names `tools/gone_ab.py` (deleted) and runs `python tools/inline.py --x`.\n"
        "```bash\n"
        "# a comment naming tools/comment.py\n"
        "JAX_PLATFORMS=cpu python -m pytest tests/test_a.py::test_b -q  # tests/in_comment.py\n"
        "chiprun --chips 4 -- python3 benchmarks/run.py --workload <cell> \\\n"
        "  --out /tmp/x.py --keep benchmarks/layers/<name>.py\n"
        "python -m perceiver_io_tpu.scripts.text.clm fit\n"
        "```\n"
    )
    names = [p for c in commands_of_markdown(text) for p in named_in_command(c)]
    assert names == [
        "tests/test_a.py", "benchmarks/run.py", "perceiver_io_tpu/scripts/text/clm.py",
        "tools/inline.py",
    ]
    source = 'run(sys.executable, "tools/a.py", "-q")\nx = ["tests/test_b.py", "c.py"]'
    assert named_in_tasks(source) == ["tools/a.py", "tests/test_b.py"]


@pytest.mark.parametrize("document", DOCUMENTS)
def test_a_documents_commands_run_files_that_exist(document):
    named = set(named_files(document))
    missing = sorted(p for p in named if not os.path.isfile(os.path.join(ROOT, p)))
    assert not missing, f"{document} gives commands that name files not in the tree: {missing}"
