"""User-docs gates in tier-1 (mirrored by the CI docs lane).

Every ``>>>`` example in README.md and docs/ must execute verbatim, the
public-API docstring examples must run, and no markdown file may carry a
broken intra-repo link.  CI runs the same checks standalone
(``pytest --doctest-glob='*.md' README.md docs`` +
``scripts/check_doc_links.py``), so a docs regression fails both lanes.
"""
import doctest
import importlib
import importlib.util
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLAGS = doctest.ELLIPSIS | doctest.NORMALIZE_WHITESPACE

MARKDOWN_WITH_DOCTESTS = [
    "README.md",
    "docs/architecture.md",
    "docs/plan-format.md",
    "docs/distributed.md",
    "docs/cost-models.md",
    "docs/serving.md",
    "docs/out-of-core.md",
    "docs/analysis.md",
    "docs/backends.md",
]

# the public API surface whose docstrings carry runnable examples
API_MODULES = [
    "repro.core.spec",
    "repro.core.planner",
    "repro.core.executor",
    "repro.core.cost",
    "repro.core.order_dp",
    "repro.core.slicing",
    "repro.autotune.cache",
    "repro.autotune.tuner",
    "repro.distributed.spttn_dist",
    "repro.spans",
]


@pytest.mark.parametrize("relpath", MARKDOWN_WITH_DOCTESTS)
def test_markdown_examples_run(relpath):
    res = doctest.testfile(os.path.join(REPO, relpath),
                           module_relative=False, optionflags=FLAGS)
    assert res.attempted > 0, f"{relpath} lost its examples"
    assert res.failed == 0, f"{relpath}: {res.failed} failing example(s)"


@pytest.mark.parametrize("modname", API_MODULES)
def test_api_docstring_examples_run(modname):
    mod = importlib.import_module(modname)
    res = doctest.testmod(mod, optionflags=FLAGS)
    assert res.attempted > 0, f"{modname} lost its docstring examples"
    assert res.failed == 0, f"{modname}: {res.failed} failing example(s)"


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_no_broken_intra_repo_links(capsys):
    mod = _load_script("check_doc_links")
    assert mod.main(["check_doc_links.py", REPO]) == 0, capsys.readouterr().out


def test_examples_use_facade_imports(capsys):
    """Mirror of the CI example-import lint: examples are the copy-paste
    surface, so they must import through the `repro` facade, not the
    implementation packages it re-exports."""
    mod = _load_script("check_example_imports")
    assert mod.main(["check_example_imports.py", REPO]) == 0, \
        capsys.readouterr().out


def test_every_doc_is_registered(capsys):
    """Mirror of the CI docs-registration lint: a docs/*.md added without
    an entry in MARKDOWN_WITH_DOCTESTS would never have its examples run,
    so it fails here and in the docs lane."""
    mod = _load_script("check_docs_registered")
    assert mod.main(["check_docs_registered.py", REPO]) == 0, \
        capsys.readouterr().out
    # the script reads the same registry this module executes
    assert set(mod.registered_docs(REPO)) == set(MARKDOWN_WITH_DOCTESTS)
