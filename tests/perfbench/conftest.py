"""The benchmark's own tests: the repo's root goes on the path so that
`perfbench` imports as it does when `perfbench/run.py` is the command."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
