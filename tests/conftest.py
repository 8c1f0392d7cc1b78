"""Test configuration.

Tests run on the XLA CPU backend with 8 virtual devices so TP/PP/EP/CP mesh
code is exercised without TPU hardware (SURVEY.md §4.3). Must be set before
jax is imported anywhere.
"""

import os

# Hard override, not setdefault: tests must never claim a chip, whatever
# the environment presets — they run on the CPU backend with 8 virtual
# devices.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Debug-mode precondition checks that are too hot for production (e.g.
# gather_kv_window's page-aligned-run assertion) fire throughout the suite.
os.environ.setdefault("DIS_TPU_DEBUG_GATHER", "1")

# ---------------------------------------------------------------------------
# Fast/slow test tiers (VERDICT r4 #9): tests listed in slow_tests.txt
# (>= 4s on a clean timing run — JAX-compile-heavy e2e/mesh tests) are
# marked `slow` at collection, and the DEFAULT run excludes them via
# pyproject addopts so the conformance tier finishes in < 5 min.
#   full suite:  python -m pytest tests/ -m "" -q
#   slow only:   python -m pytest tests/ -m slow -q
#   regenerate:  python tools/update_slowlist.py (see its docstring)
# A slowlisted test that no longer exists is ignored; NEW tests default
# to the fast tier until the next regeneration.
# ---------------------------------------------------------------------------
import os.path as _osp  # noqa: E402

import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    # a test named explicitly (`pytest tests/foo.py::test_bar`) must RUN,
    # slowlisted or not — those ITEMS skip the marking so the default
    # `-m "not slow"` addopts has nothing to deselect there. Marking is
    # per-item: directory/file args in the same invocation keep their
    # tier split.
    named = tuple(a.split("[", 1)[0] for a in config.args if "::" in a)
    path = _osp.join(_osp.dirname(__file__), "slow_tests.txt")
    try:
        with open(path) as f:
            slow = {
                ln.strip() for ln in f
                if ln.strip() and not ln.startswith("#")
            }
    except OSError:
        return
    for item in items:
        explicit = any(
            item.nodeid == n or item.nodeid.startswith(n + "[")
            for n in named
        )
        if not explicit and item.nodeid in slow:
            item.add_marker(pytest.mark.slow)
