"""95th percentile of the gap between successive output tokens, over
every gap of every request in the window (host clock).

A token is out when the window of the step that made it closes: the
profiled dispatch has synced, and the stats record and governor tick of
the step before it are inside the gap."""
import numpy as np


def read(run):
    if run.cell.traffic["entry"] != "serve":
        return None
    by_rid = {}
    for rid, _phase, _step, _t_in, t_out in run.host["windows"]:
        by_rid.setdefault(rid, []).append(t_out)
    gaps = [np.diff(np.sort(t)) for t in by_rid.values() if len(t) > 1]
    if not gaps:
        return None
    return 1e3 * float(np.percentile(np.concatenate(gaps), 95))
