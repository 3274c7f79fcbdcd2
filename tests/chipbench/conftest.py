"""The chip benchmark's own tests run on the CPU: the harness is
imported from the checkout's root, as ``benchmarks/chip/run.py`` does."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
