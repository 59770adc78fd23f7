"""CPU rehearsal of the benchmark: run from the repository's root with

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (_REPO / "src", _REPO):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
