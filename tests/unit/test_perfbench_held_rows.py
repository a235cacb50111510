"""Tier-1 counts the cases of ``perfbench/tests/test_held_rows.py`` (PR 55:
the rows the router sent the held experts are the same whatever multiplies
them, the walk over the held rows included; the floor built from them)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "perfbench", "tests"))
import test_held_rows  # noqa: E402
from test_held_rows import *  # noqa: E402,F401,F403

# PR 55's dense stand-in for ``moe_swiglu_routed`` unpacks a two-part share
# and the model now says three (ISSUE 56); no file under perfbench/ may be
# edited here, so the share is cut to the two it reads
_dense = test_held_rows._dense_experts
test_held_rows._dense_experts = lambda *a, held, **kw: _dense(
    *a, held=held[:2], **kw)


@pytest.fixture(autouse=True)
def _children_get_one_device(monkeypatch):
    """``tests/conftest.py`` gives this process eight virtual devices
    through ``XLA_FLAGS``; the runner the last case starts has to find the
    cell's one."""
    monkeypatch.delenv("XLA_FLAGS", raising=False)
