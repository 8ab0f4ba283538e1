"""The benchmark's own tests run on the CPU: ``python -m pytest bench/tests``."""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
