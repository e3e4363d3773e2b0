"""``pytest cellbench/tests -q`` from the repo root, on the CPU (run by
hand; tier-1 runs ``tests/`` only).  In-process tests share one CPU
device; the four-chip cell is rehearsed in a child process, which asks
for its own virtual devices."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
