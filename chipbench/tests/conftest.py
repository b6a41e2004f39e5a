"""Run by hand: ``python -m pytest chipbench/tests -q`` (not part of the
repo's tier-1 tests).  Everything here runs on the CPU at the
configurations' rehearsal sizes."""

import os
import sys

# chipbench/ itself; importing run.py puts the rest on the path
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
