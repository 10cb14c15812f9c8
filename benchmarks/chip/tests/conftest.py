"""Self-tests of the chip benchmark, on the CPU at tiny sizes:

    PYTHONPATH=src python -m pytest benchmarks/chip/tests -q
"""
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))


@pytest.fixture
def fixture_root(tmp_path):
    """A copy of the fixture cells (their compile cache lands in it)."""
    root = tmp_path / "fixture"
    shutil.copytree(HERE / "fixture", root)
    return root
