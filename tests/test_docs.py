"""The documents name commands that exist: every ``rtfd <sub>`` /
``python -m realtime_fraud_detection_tpu <sub>`` parses in
``cli.build_parser()``, with the ``--flags`` written beside it, and every
``python <script>.py`` is a file of the repository."""

import argparse
import re
from pathlib import Path

import pytest

from realtime_fraud_detection_tpu.cli import build_parser

REPO = Path(__file__).resolve().parent.parent
DOCUMENTS = sorted(
    [REPO / "README.md", REPO / "COMPONENTS.md", REPO / "deploy" / "README.md",
     REPO / ".claude" / "skills" / "verify" / "SKILL.md",
     *(REPO / "docs").glob("*.md")])

# a subcommand is a whole word: ``helm install rtfd deploy/helm/rtfd`` names
# a path, and ``rtfd lint's`` a possessive
COMMAND = re.compile(
    r"(?:\brtfd|python3? -m realtime_fraud_detection_tpu) +"
    r"([a-z][a-z-]*)(?![\w/.'-])([^`\n|;()]*)")
FLAG = re.compile(r"(?<![\w-])(--[a-z][a-z-]*)")
SCRIPT = re.compile(r"\bpython3? +([\w./-]+\.py)\b")


@pytest.fixture(scope="module")
def subparsers():
    action = next(a for a in build_parser()._actions
                  if isinstance(a, argparse._SubParsersAction))
    return action.choices


@pytest.mark.parametrize("path", DOCUMENTS,
                         ids=[str(p.relative_to(REPO)) for p in DOCUMENTS])
def test_a_document_names_commands_and_scripts_that_exist(path, subparsers):
    text = path.read_text()
    unknown = []
    for match in COMMAND.finditer(text):
        sub, rest = match.group(1), match.group(2)
        if sub not in subparsers:
            unknown.append(f"subcommand {sub!r} in {match.group(0)!r}")
            continue
        options = subparsers[sub]._option_string_actions
        unknown += [f"{flag} is no option of {sub!r}"
                    for flag in FLAG.findall(rest) if flag not in options]
    unknown += [f"script {name!r}" for name in SCRIPT.findall(text)
                if not (REPO / name).is_file()]
    assert not unknown, f"{path.relative_to(REPO)} names: {unknown}"
