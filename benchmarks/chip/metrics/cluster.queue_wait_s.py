"""Mean admission wait of the window's served requests: the
scheduler's submit-to-admit time (``TreeResult.wait``, the quantity
``EngineStats.mean_wait`` averages)."""


def read(run):
    waits = run.layer.get("waits")
    if not waits:
        return None
    return sum(waits) / len(waits)
