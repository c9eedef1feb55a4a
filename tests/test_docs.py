"""Every path of this repository that ``README.md`` or a ``docs/*.md``
names in backticks exists.

A token counts as such a path when it starts with one of the
repository's top-level directories; a ``:line`` or ``::test`` suffix is
cut. Bare file names are out of reach by that rule, and so are the
reference's paths written with their ``/root/reference/`` prefix."""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOP_LEVEL = ("dlrover_tpu/", "benchmark/", "tests/", "tools/", "docs/",
             "examples/")
DOCUMENTS = ["README.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))
)


def named_paths(text):
    """The repository paths among a document's backticked tokens."""
    found = set()
    for token in re.findall(r"`([^`\n]+)`", text):
        if not token.startswith(TOP_LEVEL):
            continue
        # `tests/test_x.py::TestY`, `docs/x.md:12-14`, `tools/x.py --flag`
        path = re.split(r"[:\s#(]", token, maxsplit=1)[0]
        if re.search(r"[*<>{}…]|\.\.\.", path):
            continue    # a pattern or a placeholder, not one path
        found.add(path)
    return found


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_named_path_exists(document):
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    missing = sorted(
        p for p in named_paths(text)
        if not os.path.exists(os.path.join(ROOT, p))
    )
    assert not missing, f"{document} names paths that do not exist: {missing}"


def test_the_rule_reads_a_path_with_its_suffixes():
    text = ("`tests/test_x.py::TestY::test_z` `docs/a.md:12-14` "
            "`tools/x.py --flag` `setup.py` `/root/reference/docs/b.md` "
            "`benchmark/layer_metrics/<name>.py` `dlrover_tpu/ops/`")
    assert named_paths(text) == {
        "tests/test_x.py", "docs/a.md", "tools/x.py", "dlrover_tpu/ops/",
    }
