import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# the benchmark's tests run on the CPU, with Pallas in interpret mode
os.environ.setdefault("JAX_PLATFORMS", "cpu")
