"""The benchmark's own tests run on the CPU, at the configurations' tiny
rehearsal sizes, with the program's kernels in interpret mode."""
import os
import sys
from pathlib import Path

os.environ.setdefault("REPRO_PALLAS_INTERPRET", "1")

_SRC = str(Path(__file__).resolve().parents[1] / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
