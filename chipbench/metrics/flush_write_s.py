"""Host seconds in ``Profiler.flush()`` and ``write()`` after the window."""


def read(run):
    return run.host.get("flush_write_s")
