"""The benchmark's CPU tests import ``bench`` from the checkout's root."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p in sys.path:
        sys.path.remove(p)
    sys.path.insert(0, p)
