"""perfbench's own tests run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
