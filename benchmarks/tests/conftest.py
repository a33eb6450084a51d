"""benchmarks/tests: the yardstick's own tests.  They run on the sandbox's
CPU, compile no kernel, and are not part of tier-1 (`pytest tests/`):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import os
import sys

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
