"""Host self time of the executor's extend-add stage
(``executor.extend_add``: the children's Schur complements folded into each
dispatch's fronts), in seconds per factorization of the traced window
(``program_spans.py``)."""
import runpy
from pathlib import Path

SPANS = runpy.run_path(str(Path(__file__).resolve().parents[1] / "program_spans.py"))


def read(run):
    return SPANS["stage_seconds"](run, ("executor.extend_add",))
