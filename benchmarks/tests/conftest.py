"""The benchmark's own tests: a CPU rehearsal at a tiny size, explicit
and apart from ``run.py``'s measured path. Run them with

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q -p no:cacheprovider

They are not part of the repository's tier-1 run (``pytest tests/``)."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
