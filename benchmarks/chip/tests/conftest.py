"""The benchmark's own tests, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

They are not under the repository's ``tests/``, so the tier-1 run does
not collect them."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))
