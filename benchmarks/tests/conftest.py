"""The benchmark's own tests run on the CPU: force it before JAX loads (the
repo's tests/conftest.py does the same for tier-1)."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
