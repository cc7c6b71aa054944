"""The benchmark's own tests (not part of tier-1): `python -m pytest benchmark/tests -q`."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
