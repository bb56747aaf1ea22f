"""The documents name what the tree holds: every `python <path>.py` command
they carry runs a file that is there, and every `PADDLE_TPU_*` name they
carry is read by some python file."""
import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMMAND = re.compile(r"\bpython3?\s+([\w./-]+\.py)\b")
ENV_NAME = re.compile(r"PADDLE_TPU_[A-Z0-9_]+")
# the user's own script in the README's fault-injection recipe
NOT_OURS = {"train.py"}
# what running, testing and the chip tool leave behind (.gitignore)
SKIP_DIRS = {".git", ".chip_export", "chiprun_out", ".jax_cache",
             "__pycache__", ".pytest_cache", ".hypothesis", ".bench_trace"}


@functools.lru_cache(maxsize=None)
def _env_names_in_python():
    names = set()
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in SKIP_DIRS]
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f), errors="replace") as fh:
                    names.update(ENV_NAME.findall(fh.read()))
    return names


@pytest.mark.parametrize("doc", ["README.md", "BASELINE.md",
                                 "DESIGN_DECISIONS.md", "PERF.md"])
def test_document_names_what_the_tree_holds(doc):
    with open(os.path.join(REPO, doc)) as fh:
        text = fh.read()
    missing = sorted({p for p in COMMAND.findall(text)
                      if p not in NOT_OURS
                      and not os.path.isfile(os.path.join(REPO, p))})
    assert not missing, f"{doc} runs files that are not in the tree"
    orphans = sorted(set(ENV_NAME.findall(text)) - _env_names_in_python())
    assert not orphans, f"{doc} names switches no python file reads"
