"""The commands the README and ``docs/`` tell a reader to run name files
that exist: a script that is retired takes its lines out of the documents."""

import re
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
DOCS = (REPO / "README.md", *sorted((REPO / "docs").glob("*.md")))

# ``python <file>.py`` / ``python -m <module>``, then the rest of the line
_COMMAND = re.compile(r"python3? +(?:-m +([\w.]+)|([\w./-]+\.py))([^`|#\n]*)")


def _command_lines(text: str):
    """Lines of fenced code blocks and of tables (the launch table)."""
    fenced = False
    for line in text.splitlines():
        if line.lstrip().startswith("```"):
            fenced = not fenced
        elif fenced or line.lstrip().startswith("|"):
            yield line


def test_documented_commands_name_files_that_exist():
    missing, seen = [], 0
    for doc in DOCS:
        for line in _command_lines(doc.read_text()):
            for module, script, rest in _COMMAND.findall(line):
                seen += 1
                paths = [script] if script else []
                # a module of this repo (not pytest): its file or its package
                if module and (REPO / module.split(".")[0]).exists():
                    path = module.replace(".", "/")
                    if not (REPO / path / "__init__.py").is_file():
                        paths.append(path + ".py")
                paths += [a for a in rest.split() if a.endswith(".py")]
                missing += [
                    (doc.name, p) for p in paths if not (REPO / p).is_file()
                ]
    assert seen >= 10, "the pattern no longer finds the documents' commands"
    assert not missing, missing


def test_readme_names_the_instrument():
    """What decides every PR is in the README by name."""
    readme = (REPO / "README.md").read_text()
    for name in ("benchmarks/run.py", "BENCHMARK.json", "PERF.md",
                 "PERF_LEDGER.jsonl"):
        assert name in readme, name
