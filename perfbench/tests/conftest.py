"""CPU rehearsal tests of the benchmark's own files (not part of tier-1):

    python -m pytest perfbench/tests -q

They run at tiny sizes on the CPU, where the program's search takes its XLA
scan; nothing here reads a device metric.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)
for p in (PERFBENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _cache_in_tmp(tmp_path, monkeypatch):
    """Keep CPU programs out of the checkout's compile cache: an entry
    written here would travel to the chip and stop all writes there
    (PERF.md, PR 23 Finding 4)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxcache"))
