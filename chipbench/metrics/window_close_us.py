"""Mean host time of closing a decode window (the program's
``serving.close`` span: the stats record and the governor tick), in the
window."""
from chipbench import program_spans


def read(run):
    return program_spans.mean_us(
        program_spans.in_window(run, "serving.close", phase="decode"))
