import os
import sys
from pathlib import Path

# tests run on the CPU: a test process must never take the chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
