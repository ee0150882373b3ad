"""Kernel dispatches per factorization in the window
(``ExecutionReport.n_dispatches``, averaged over the window's
factorizations: the async executor coalesces ready fronts as they land,
so the count can vary from one factorization to the next)."""


def read(run):
    counts = run.layer.get("dispatches")
    if not counts:
        return None
    return sum(counts) / len(counts)
