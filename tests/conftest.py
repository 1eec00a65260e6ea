"""Shared pytest setup: make tests/ sibling modules importable.

pytest's rootdir insertion usually handles this, but the explicit insert
keeps ``from subproc import run_sub`` working under any invocation style
(``pytest tests/...``, ``python -m pytest`` from a parent dir, IDE runners).
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clear_bucket_layout_cache():
    """Keep compile-time caches from leaking across tests.

    Layouts/plans are keyed on tree structure and retain PyTreeDefs, so
    parametrised mesh/model sweeps would otherwise accumulate entries for
    the whole session; clearing per test also keeps cache-hit assertions
    (tests/test_bucketing.py) independent of test order.
    ``plan.clear_plan_cache()`` is the single delegating entry point — it
    clears the plan/shard-struct caches, both budget sweeps, and
    ``bucketing``'s layout cache.
    """
    yield
    from repro.core import plan
    plan.clear_plan_cache()
