"""The benchmark's own tests: CPU only, small sizes. The checkout root goes
on ``sys.path`` so ``import bench`` resolves like it does for
``bench/run.py``."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
