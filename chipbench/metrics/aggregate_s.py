"""Host seconds in ``aggregate()`` of the window's profiles and traces."""


def read(run):
    return run.host.get("aggregate_s")
