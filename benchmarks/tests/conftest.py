"""The harness's own CPU tests. Run by path (``python -m pytest
benchmarks/tests``); they are not part of the repository's tier-1 suite."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
