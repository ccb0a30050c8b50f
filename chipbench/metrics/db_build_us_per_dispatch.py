"""Host time from the end of the window to a queryable database
(``flush()``, ``write()``, ``aggregate()`` with the program's defaults),
per dispatch the window recorded."""


def read(run):
    n = run.host.get("dispatches")
    if not n or "db_build_s" not in run.host:
        return None
    return 1e6 * run.host["db_build_s"] / n
