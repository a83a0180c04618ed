"""CPU tests of the chip benchmark: they drive runs at toy sizes with
the device check off, and never load the TPU library."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
