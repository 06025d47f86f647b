"""One yardstick, and documents whose commands exist.

The repo measures with ``benchmark/run.py`` (``BENCHMARK.json``) and
nothing else: the round records of the retired CPU bench stay gone, the
operator tools that remain import, and every command a standing document
gives names a file or module that is there. Only commands are read (code
blocks and code spans that start a ``python`` invocation), so history
told in prose may name what is gone.
"""

import glob
import importlib
import importlib.util
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: ``python[3] path/to/file.py`` or ``python[3] -m dotted.module``
_COMMAND = re.compile(
    r"\bpython3?[ \t]+(?:-m[ \t]+([A-Za-z_][\w.]*)|([\w./-]+\.py)\b)")
_FENCE = re.compile(r"^```.*?^```", re.S | re.M)
_SPAN = re.compile(r"`([^`\n]+)`")


def _commands(text: str) -> set[tuple[str, str]]:
    """("module" | "file", name) of every python command in the code
    blocks and code spans of a markdown text."""
    code = _FENCE.findall(text)
    code += _SPAN.findall(_FENCE.sub("", text))
    found = set()
    for piece in code:
        for module, path in _COMMAND.findall(piece):
            found.add(("module", module) if module else ("file", path))
    return found


def _exists(kind: str, name: str) -> bool:
    if kind == "file":
        return os.path.isfile(os.path.join(REPO, name))
    top = name.split(".")[0]
    if not os.path.isdir(os.path.join(REPO, top)):
        return importlib.util.find_spec(top) is not None   # installed
    base = os.path.join(REPO, *name.split("."))
    return os.path.isfile(base + ".py") or os.path.isfile(
        os.path.join(base, "__main__.py"))


@pytest.mark.parametrize("document", [
    "README.md", "PERF.md", ".claude/skills/verify/SKILL.md"])
def test_every_command_a_document_gives_names_something_that_exists(
        document):
    with open(os.path.join(REPO, document), encoding="utf-8") as f:
        commands = _commands(f.read())
    assert commands, f"{document} gives no python command at all"
    missing = sorted(name for kind, name in commands
                     if not _exists(kind, name))
    assert not missing, f"{document} runs what is not there: {missing}"


def test_no_round_record_at_the_root():
    """``SERVING_r04.json`` and its kind were CPU rates of a bench that
    is gone; the driver's record is ``PERF_LEDGER.jsonl``."""
    assert not glob.glob(os.path.join(REPO, "*_r[0-9][0-9].json"))


TOOLS = sorted(os.path.basename(p)[:-3]
               for p in glob.glob(os.path.join(REPO, "tools", "*.py")))


@pytest.mark.parametrize("tool", TOOLS)
def test_every_tool_left_imports(tool):
    assert importlib.import_module(f"tools.{tool}").main
