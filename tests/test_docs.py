"""The documentation is executable: every fenced ``python`` block in
docs/api.md and README.md runs top-to-bottom (blocks in one file share a
namespace), and the ``>>>`` examples in module docstrings pass doctest.
CI runs this file as its own job (see .github/workflows/ci.yml `docs`)."""

import doctest
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent

# markdown files whose ```python blocks must execute cleanly, in order
EXECUTABLE_DOCS = ["docs/api.md", "docs/serving.md", "docs/sae.md",
                   "docs/observability.md", "README.md"]

# modules whose docstring ``>>>`` examples must pass (and exist)
DOCTEST_MODULES = ["repro.core.plan", "repro.obs.metrics"]
# modules doctested opportunistically (no examples required yet)
DOCTEST_OPTIONAL = ["repro.core.ball", "repro.core.multilevel",
                    "repro.core.bilevel", "repro.serving.engine",
                    "repro.obs.jax_bridge", "repro.obs.profile"]

_FENCE = re.compile(r"^```python\s*$(.*?)^```\s*$", re.M | re.S)


def _blocks(relpath: str):
    text = (ROOT / relpath).read_text()
    return [(m.start(), m.group(1)) for m in _FENCE.finditer(text)]


@pytest.mark.parametrize("relpath", EXECUTABLE_DOCS)
def test_markdown_python_blocks_execute(relpath):
    blocks = _blocks(relpath)
    assert blocks, f"{relpath} has no ```python blocks"
    text = (ROOT / relpath).read_text()
    ns = {}
    for start, code in blocks:
        line = text.count("\n", 0, start) + 1
        try:
            exec(compile(code, f"{relpath}:{line}", "exec"), ns)  # noqa: S102
        except Exception as e:  # pragma: no cover - the assertion IS the test
            raise AssertionError(
                f"{relpath} block at line {line} failed: {e!r}") from e


@pytest.mark.parametrize("modname", DOCTEST_MODULES + DOCTEST_OPTIONAL)
def test_module_doctests(modname):
    mod = __import__(modname, fromlist=["_"])
    results = doctest.testmod(mod, verbose=False)
    assert results.failed == 0, f"{modname}: {results.failed} doctest failures"
    if modname in DOCTEST_MODULES:
        assert results.attempted > 0, f"{modname} lost its doctest examples"
