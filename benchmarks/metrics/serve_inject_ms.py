"""Median host time to inject one member into a freed slot at refill,
in milliseconds: the ``serve.inject`` spans of the program's own span
table (``jaxstream.obs.trace.SPANS``, this process) — the member
placed on its slot's chip (uploaded there under member placement) and
the inject dispatched.  The table covers the whole run, not only the
traced window.  None where the program keeps no such span.  Moves
``request_p95_s``."""


def read(layer):
    from jaxstream.obs import trace

    table = getattr(trace, "SPANS", None)
    if table is None:
        return None
    s = table.summary()["spans"].get("serve.inject")
    if not s or not s["count"]:
        return None
    return 1e3 * s["median_s"]
