"""The benchmark's own tests, run by path: ``python -m pytest chipbench/tests``.

They run on the CPU; ``run.py`` itself refuses to, so the cell tests call
the harness's functions directly at tiny sizes.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (ROOT, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)
