"""Median host time to build one request's initial state at refill or
pack, in milliseconds: the ``serve.ic_build`` spans of the program's
own span table (``jaxstream.obs.trace.SPANS``, this process), whether
built at the pack, while the segment runs or inside the boundary.  The
table covers the whole run, not only the traced window.  None where
the program keeps no such table.  Moves ``request_p95_s``."""


def read(layer):
    from jaxstream.obs import trace

    table = getattr(trace, "SPANS", None)
    if table is None:
        return None
    s = table.summary()["spans"].get("serve.ic_build")
    if not s or not s["count"]:
        return None
    return 1e3 * s["median_s"]
