"""Mean host time of the jitted decode call until it returns, with the
release of the step's previous KV cache (the program's ``serve.enqueue``
span), in the window.  The runtime enqueues the program on a thread of
its own shortly after."""
from chipbench import program_spans


def read(run):
    return program_spans.mean_us(
        program_spans.in_window(run, "serve.enqueue", phase="decode"))
