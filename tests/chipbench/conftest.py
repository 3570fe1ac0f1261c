"""The checkout on sys.path for the benchmark's CPU tests, and a scratch
benchmark root that holds small cells."""
import sys
from pathlib import Path

import pytest

CHECKOUT = Path(__file__).resolve().parents[2]
if str(CHECKOUT) not in sys.path:
    sys.path.insert(0, str(CHECKOUT))

from chipbench_roots import scratch_root  # noqa: E402


@pytest.fixture
def cpu_root(tmp_path):
    return scratch_root(tmp_path)
