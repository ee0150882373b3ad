"""Host self time of the executor's bookkeeping: ``session.execute``,
``executor.run``, ``executor.prologue``, ``executor.schedule`` and
``executor.epilogue`` outside the stages nested in them (plan conversion,
priority tables, ready-set grouping, device allocation, submission, the
report), in seconds per factorization of the traced window
(``program_spans.py``)."""
import runpy
from pathlib import Path

SPANS = runpy.run_path(str(Path(__file__).resolve().parents[1] / "program_spans.py"))


def read(run):
    return SPANS["stage_seconds"](run, (
        "session.execute", "executor.run", "executor.prologue",
        "executor.schedule", "executor.epilogue",
    ))
