"""The documents' quoted commands name files that exist.

Every ``python[3] <path>.py`` that ``README.md`` or the verify skill quotes,
in a fenced block or inline, must name a file of the checkout: a document
that still tells its reader to run a deleted script fails here.
"""

import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
COMMAND = re.compile(r"\bpython3?\s+((?:[\w.-]+/)*[\w.-]+\.py)\b")


@pytest.mark.parametrize("doc", ["README.md",
                                 ".claude/skills/verify/SKILL.md"])
def test_quoted_commands_exist(doc):
    quoted = COMMAND.findall((REPO / doc).read_text(encoding="utf-8"))
    assert quoted, f"{doc} quotes no command at all"
    missing = sorted({p for p in quoted if not (REPO / p).is_file()})
    assert not missing, f"{doc} quotes commands whose files are gone: {missing}"
