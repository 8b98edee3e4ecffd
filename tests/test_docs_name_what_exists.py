"""Every tool and script that README.md, PERF.md and the verify skill name
exists: a deleted or renamed tool fails a case here instead of leaving a
stale paragraph.  One case a name; nothing is run.  CHANGES.md and
ROADMAP.md keep history and are not read.
"""
import importlib.util
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
DOCS = ("README.md", "PERF.md", ".claude/skills/verify/SKILL.md")

MODULE = re.compile(r"python3? -m ([\w.]+)")          # python -m tools.<x>
SCRIPT = re.compile(r"python3? ([\w./-]+\.py)\b")     # python3 <file>.py
TOOL = re.compile(r"\btools[/.]([a-z_]+)\b")           # tools/<x>.py, tools.<x>


def _named():
    modules, scripts = set(), set()
    for doc in DOCS:
        text = (REPO / doc).read_text()
        modules |= set(MODULE.findall(text))
        modules |= {f"tools.{t}" for t in TOOL.findall(text)}
        scripts |= set(SCRIPT.findall(text))
    return sorted(modules), sorted(scripts)


MODULES, SCRIPTS = _named()


@pytest.mark.parametrize("module", MODULES)
def test_the_module_a_document_names_exists(module):
    # PERF.md speaks of the benchmark's own tools from inside its directory
    path = REPO / "benchmarks" / (module.replace(".", "/") + ".py")
    assert importlib.util.find_spec(module) is not None or path.is_file()


@pytest.mark.parametrize("script", SCRIPTS,
                         ids=lambda s: s.replace("/", "."))
def test_the_script_a_document_names_exists(script):
    assert (REPO / script).is_file()


def test_the_documents_name_both_kinds():
    assert "tools.autoplan" in MODULES and "benchmarks/run.py" in SCRIPTS
