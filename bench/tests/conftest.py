import os
import sys
from pathlib import Path

# the benchmark's own tests run on the CPU, at tiny sizes
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
