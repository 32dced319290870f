"""The benchmark's own tests run on the CPU: `python -m pytest bench/tests -q`.
Nothing here looks for a chip or prints a device metric."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
