"""Generated tokens completed in the window over the whole window (host
clock): every prefill gives each request of its batch its first token,
every decode step one more."""


def read(run):
    if run.cell.traffic["entry"] != "serve" or run.window_s <= 0:
        return None
    return run.host["batch"] * len(run.host["windows"]) / run.window_s
