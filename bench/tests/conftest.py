import os
import sys
from pathlib import Path

# The benchmark's own tests run on the CPU at tiny sizes; the chip is for
# bench/run.py alone.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
