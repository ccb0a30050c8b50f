"""Share of the traced window in which no operation ran on the device
(1 - union of device-op intervals / window), in a serving cell."""


def read(run):
    if run.trace is None or run.cell.traffic["entry"] != "serve":
        return None
    return run.trace.idle_pct()
