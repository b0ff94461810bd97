import os
import sys

CHIPBENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(CHIPBENCH)
for p in (CHIPBENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)
