"""Roofline share of the held experts in the decode program: the least
time the chip could take for their three grouped matmuls (the larger of
the FLOPs of the rows routed to them over peak FLOP/s and their weights
plus those rows over peak bytes/s, a step) over the device time of the
decode program's operations under the ``moe_experts`` scope.

Rows a step are those the ``serve.moe_load`` spans of the window count,
over the decode steps of their batches; without such spans, those of
uniform routing (``routed_rows``).  The reference's ``expert_flops``
and ``expert_io_bytes`` count the work.  A program without held experts
returns None."""
from chipbench import op_scopes, program_spans

PROGRAM = "decode_step"
SCOPES = ("moe_experts",)


def rows_per_step(run):
    """Rows routed to the held experts a decode step, over all layers."""
    h, ref, conf = run.host, run.cell.reference, run.cell.config
    spans = program_spans.in_window(run, "serve.moe_load")
    rows = sum(int(v) for s in spans for k, v in s.args.items()
               if k.startswith("expert_"))
    if spans and rows:
        return rows / (len(spans) * (h["gen_len"] - 1))
    return ref.routed_rows(conf, h["batch"])


def read(run):
    ref, conf, h = run.cell.reference, run.cell.config, run.host
    if run.trace is None or not hasattr(ref, "expert_io_bytes"):
        return None
    got = op_scopes.scoped_seconds(run, PROGRAM, SCOPES)
    if got is None or got[0] <= 0:
        return None
    seconds, n = got
    rows = rows_per_step(run)
    step = max(ref.expert_flops(conf, rows) / run.peaks["bf16_flops_per_s"],
               ref.expert_io_bytes(conf, rows)
               / run.peaks["hbm_bytes_per_s"])
    return 100.0 * step * n / seconds
