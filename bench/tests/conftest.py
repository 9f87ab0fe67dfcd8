"""The benchmark's own tests: ``pytest bench/tests`` from the checkout root,
on the CPU (``JAX_PLATFORMS=cpu``)."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
