"""The benchmark's tests import it as the ``bench`` package from the repo
root, and share the throwaway-root helpers of ``cellkit.py``."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for p in (REPO, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
