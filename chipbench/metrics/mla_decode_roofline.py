"""Roofline share of the decode program's latent attention: the least
time the chip could take for it over the decode steps of the traced
window (per step, the larger of its needed FLOPs over peak FLOP/s and
its needed bytes over peak bytes/s) over the device time of the
decode program's operations under the ``mla_*`` scopes.

Needed bytes are the attention weights once and the latent cache up to
each step's position (rows read, the new row written); the reference's
``mla_decode_flops``/``mla_decode_bytes`` count them.  A program
without latent attention has no such operations, and the reader
returns None."""
from chipbench import op_scopes

PROGRAM = "decode_step"
SCOPES = ("mla_q", "mla_absorb", "mla_scores", "mla_out")


def read(run):
    ref, conf, h = run.cell.reference, run.cell.config, run.host
    if run.trace is None or not hasattr(ref, "mla_decode_bytes"):
        return None
    got = op_scopes.scoped_seconds(run, PROGRAM, SCOPES)
    if got is None or got[0] <= 0:
        return None
    seconds, n = got
    steps = [w[2] for w in h["windows"] if w[1] == "decode"][:n]
    if not steps:
        return None
    need = 0.0
    for step in steps:
        pos = h["prompt_len"] + step
        need += max(ref.mla_decode_flops(conf, h["batch"], pos)
                    / run.peaks["bf16_flops_per_s"],
                    ref.mla_decode_bytes(conf, h["batch"], pos)
                    / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need * n / len(steps) / seconds
