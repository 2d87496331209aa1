"""The benchmark's own tests: CPU, rehearsal sizes, the same code as a
chip run. Run them with ``python -m pytest benchmark/tests -q -p
no:cacheprovider`` (the tier-1 command collects ``tests/`` only)."""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
