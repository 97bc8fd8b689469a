"""What the documents tell a reader to pass to ``DecodeEngine(`` is a
parameter of ``DecodeEngine.__init__``: an option that is deleted, or
was never there, fails here and not in the reader's hands."""
import ast
import inspect
import os
import re

import pytest

from elephas_tpu import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "docs/sources/serving-guide.md",
             "examples/http_serving.py"]

_CALL = "DecodeEngine("


def _keywords_of_calls(source: str):
    """Keywords of every ``DecodeEngine(...)`` call in Python source."""
    return {kw.arg
            for node in ast.walk(ast.parse(source))
            if isinstance(node, ast.Call)
            and getattr(node.func, "id", getattr(node.func, "attr", None))
            == "DecodeEngine"
            for kw in node.keywords if kw.arg is not None}


def _keywords_of_text(text: str):
    """``name=`` at the top level of every ``DecodeEngine(`` in text
    that need not parse (`` `DecodeEngine(max_queue=, ...)` ``)."""
    found = set()
    for start in (m.end() for m in re.finditer(re.escape(_CALL), text)):
        depth, end = 1, start
        while end < len(text) and depth:
            depth += {"(": 1, "[": 1, ")": -1, "]": -1}.get(text[end], 0)
            end += 1
        flat = re.sub(r"\([^()]*\)|\[[^\[\]]*\]", "", text[start:end - 1])
        found.update(re.findall(r"\b([A-Za-z_]\w*)\s*=(?!=)", flat))
    return found


def documented_keywords(path: str):
    with open(os.path.join(ROOT, path)) as f:
        text = f.read()
    if path.endswith(".py"):
        return _keywords_of_calls(text)
    found = set()
    for block in re.findall(r"```python\n(.*?)```", text, re.S):
        try:
            found |= _keywords_of_calls(block)
        except SyntaxError:          # a sketch with placeholders
            found |= _keywords_of_text(block)
    for span in re.findall(r"`([^`\n]*)`", re.sub(r"```.*?```", "", text,
                                                  flags=re.S)):
        found |= _keywords_of_text(span)
    return found


def test_the_scanner_reads_what_does_not_parse():
    assert _keywords_of_text(
        "`DecodeEngine(max_queue=, paged=(nb, bs), f(x=1)[a=2])`, "
        "`submit(deadline_ms=)`") == {"max_queue", "paged"}


@pytest.mark.parametrize("path", DOCUMENTS)
def test_documented_engine_options_exist(path):
    documented = documented_keywords(path)
    assert documented, f"{path} passes no keyword to {_CALL}...)"
    parameters = set(inspect.signature(DecodeEngine.__init__).parameters)
    assert documented <= parameters, (
        f"{path} documents DecodeEngine options that do not exist: "
        f"{sorted(documented - parameters)}")
