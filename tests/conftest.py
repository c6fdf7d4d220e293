"""Test bootstrap: puts `src/` on sys.path so `python -m pytest` works with
or without PYTHONPATH=src (the tier-1 command in ROADMAP.md sets it; CI and
bare local runs may not)."""
import pathlib
import sys

_SRC = pathlib.Path(__file__).resolve().parents[1] / "src"
if str(_SRC) not in sys.path:
    sys.path.insert(0, str(_SRC))
