"""Roofline share of the decode program: the least time the chip could
take for the decode steps of the traced window (per step, the larger of
needed FLOPs over peak FLOP/s and needed bytes over peak bytes/s) over
their device time in the trace.

Needed bytes are the weights read once, the KV cache up to each step's
position, the new K and V, and the logits; a copy of the whole cache is
not needed.  At the decode cell's sizes the bytes bound."""

PROGRAM = "decode_step"


def read(run):
    if run.trace is None or run.cell.traffic["entry"] != "serve":
        return None
    times = run.trace.program_times(PROGRAM)
    h, ref, conf = run.host, run.cell.reference, run.cell.config
    steps = [w[2] for w in h["windows"] if w[1] == "decode"]
    n = min(len(times), len(steps))
    if n == 0:
        return None
    need = 0.0
    for step in steps[:n]:
        pos = h["prompt_len"] + step
        need += max(ref.decode_flops(conf, h["batch"], pos)
                    / run.peaks["bf16_flops_per_s"],
                    ref.decode_bytes(conf, h["batch"], pos)
                    / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need / float(times[:n].sum())
