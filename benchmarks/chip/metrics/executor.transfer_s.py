"""Host self time of the executor's transfers on its worker threads:
``executor.h2d`` (``device_put`` of a batch) and ``executor.d2h`` (the
factored batch copied back), in seconds per factorization of the traced
window (``program_spans.py``)."""
import runpy
from pathlib import Path

SPANS = runpy.run_path(str(Path(__file__).resolve().parents[1] / "program_spans.py"))


def read(run):
    return SPANS["stage_seconds"](run, ("executor.h2d", "executor.d2h"))
