"""Share of the device's idle time in the traced window, in %, in which the
main thread is in no program span (``session.*``, ``executor.*``): the
idle time that no stage of the program names (``program_spans.py``)."""
import runpy
from pathlib import Path

SPANS = runpy.run_path(str(Path(__file__).resolve().parents[1] / "program_spans.py"))


def read(run):
    return SPANS["unattributed_idle_pct"](run)
