"""Host self time of the executor's packing stages: ``executor.pack``
(fronts padded to their shape class and stacked into a batch) and
``executor.unpack`` (a finished dispatch's panels and Schur complements
taken apart and stored, and its parents made ready), in seconds per
factorization of the traced window (``program_spans.py``)."""
import runpy
from pathlib import Path

SPANS = runpy.run_path(str(Path(__file__).resolve().parents[1] / "program_spans.py"))


def read(run):
    return SPANS["stage_seconds"](run, ("executor.pack", "executor.unpack"))
