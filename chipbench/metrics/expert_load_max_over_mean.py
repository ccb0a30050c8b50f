"""How unevenly the router loads the experts held here: rows routed to
the busiest held expert over the mean of the held experts, summed over
the ``serve.moe_load`` spans in the window (one a batch: the decode
program's own counter, read after the batch's last step, one
``expert_<id>`` arg per held expert).  1 is an even load; a program
without held experts emits no such span."""
from chipbench import program_spans

PREFIX = "expert_"


def held_rows(run):
    """{expert id: rows routed to it} over the window's spans."""
    total = {}
    for s in program_spans.in_window(run, "serve.moe_load"):
        for k, v in s.args.items():
            if k.startswith(PREFIX):
                total[k] = total.get(k, 0) + int(v)
    return total


def read(run):
    rows = list(held_rows(run).values())
    if not rows or sum(rows) <= 0:
        return None
    return max(rows) * len(rows) / sum(rows)
