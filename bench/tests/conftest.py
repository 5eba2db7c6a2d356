"""Shared sizing for the benchmark's self-tests: the cells' own
configurations at a size a CPU test run can hold."""

import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def small(cfg: dict, messages: int = 1 << 14, users: int = 4096) -> dict:
    """Overrides that shrink a configuration to ``messages`` and
    ``users`` while keeping about 8 flushed components per partition."""
    ds = copy.deepcopy(cfg["datasets"])
    parts = int(cfg["partitions"])
    ds["MugshotMessages"]["flush_threshold"] = max(messages // parts // 8, 1)
    ds["MugshotUsers"]["flush_threshold"] = max(users // parts // 8, 1)
    return {"messages": messages, "users": users, "datasets": ds}


@pytest.fixture(scope="session")
def spec():
    from bench import run
    return run.load_spec()
