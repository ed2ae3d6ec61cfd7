"""Every knob is alive, and the names this tree retired stay retired.

A knob is alive when some program file other than ``core/options.py``
reads it off a ``Knobs``: as an attribute of one of the names the tree
gives a ``Knobs`` (``knobs``, ``_knobs``, ``kn``, ``k``, ``Knobs()``,
``DEFAULT_KNOBS``, ``_effective_knobs()``) or by name
(``getattr(….knobs, "name", …)``). A field of the same name on another
object (``self.pipeline_depth``) or a dict key does not count. A knob
nothing reads is an option that selects nothing: delete it with its
comment.

The retired names are those of the CPU harness and the fused Pallas
kernel that PR 32 deleted. The walk is over what the program owns
(``OWNED``): the package, the tests, the root's entry points, the two
documents that describe the system and the verify skill. The records
that tell the history (``CHANGES.md``, ``PERF.md``, ``ROADMAP.md``), the
driver's papers (``ISSUE.md``, ``REVIEW.md``, whatever else it drops in
the checkout) and the benchmark's own files (which only a ``benchmark``
PR may edit) are not in it. The walk is over the directory, not
``git ls-files``: the benchmark runs from ``git archive`` trees that
have no ``.git``.
"""

import dataclasses
import os
import re

import pytest

from foundationdb_tpu.core.options import Knobs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OWNED = ("foundationdb_tpu", "tests", "__graft_entry__.py", "chip_smoke.py",
         "README.md", "SURVEY.md", os.path.join(".claude", "skills"))
# what the tree calls a Knobs where it reads one
RECEIVER = r"(?:\b_?knobs?|\bkn|\bk|\bKnobs\(\)|\bDEFAULT_KNOBS|_knobs\(\))"
RETIRED = ("bench.py", "BENCH_MODE", "benchdiff", "pallas_scan",
           "BENCH_r0", "MULTICHIP_r0")


def _files(top, suffixes):
    if os.path.isfile(top):
        yield top
    for dirpath, dirnames, filenames in os.walk(top):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            if f.endswith(suffixes):
                yield os.path.join(dirpath, f)


def _read(path):
    with open(path, encoding="utf-8") as f:
        return f.read()


@pytest.fixture(scope="module")
def program_text():
    package = os.path.join(ROOT, "foundationdb_tpu")
    options = os.path.join(package, "core", "options.py")
    return "\n".join(_read(p) for p in _files(package, (".py",))
                     if p != options)


@pytest.mark.parametrize("knob", [f.name for f in dataclasses.fields(Knobs)])
def test_every_knob_is_read_by_the_program(program_text, knob):
    read = r"""%s\.%s\b|getattr\([^,()]*knobs?,\s*["']%s["']""" % (
        RECEIVER, knob, knob)
    assert re.search(read, program_text), (
        f"no file under foundationdb_tpu/ reads Knobs.{knob}")


@pytest.fixture(scope="module")
def tree_text():
    me = os.path.abspath(__file__)
    return {os.path.relpath(p, ROOT): _read(p)
            for top in OWNED
            for p in _files(os.path.join(ROOT, top), (".py", ".md"))
            if p != me}


@pytest.mark.parametrize("name", RETIRED)
def test_retired_names_stay_out_of_the_tree(tree_text, name):
    holders = [p for p, text in tree_text.items() if name in text]
    assert not holders, f"{name!r} is still named in {holders}"
