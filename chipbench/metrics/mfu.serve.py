"""Model FLOPs of every prompt and generated token the traced window
served, over the window, over the chips' bf16 peak.

Operations per step come from the configuration's reference file: all
prompt tokens (causal attention, logits at the last one) for a prefill,
one token per request at its position for a decode step."""


def read(run):
    if run.trace is None or run.cell.traffic["entry"] != "serve" \
            or run.trace.window_s <= 0:
        return None
    ref, conf, h = run.cell.reference, run.cell.config, run.host
    flops = 0.0
    for _rid, phase, step, _t_in, _t_out in h["windows"]:
        if phase == "prefill":
            flops += ref.prefill_flops(conf, h["batch"], h["prompt_len"])
        else:
            flops += ref.decode_flops(conf, h["batch"],
                                      h["prompt_len"] + step)
    peak = run.peaks["bf16_flops_per_s"] * run.device["count"]
    return 100.0 * flops / run.trace.window_s / peak
