"""The benchmark's own tests run on the CPU, by hand:
``pytest benchmark/tests -q``. They rehearse paths and check arithmetic; a
time, a rate or a share measured here is never reported anywhere."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
