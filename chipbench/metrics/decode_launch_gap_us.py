"""Mean device-idle time between one decode program's end and the next
one's start, within a batch (pairs with a prefill between them are left
out), from the trace.  Other programs between two decode steps (the
sampling's argmax) count as busy, not idle."""
import numpy as np

PROGRAM = "decode_step"
BATCH_START = "prefill_step"


def read(run):
    if run.trace is None or run.cell.traffic["entry"] != "serve":
        return None
    gaps = run.trace.gaps_between(PROGRAM, unless=(BATCH_START,))
    if not len(gaps):
        return None
    return 1e6 * float(np.mean(gaps))
