"""The harness's own tests run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (os.path.dirname(HERE),
             os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")):
    if path not in sys.path:
        sys.path.insert(0, path)
