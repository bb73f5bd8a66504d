"""Median host time between one segment's end and the next segment's
dispatch, in milliseconds, from the program's own span table
(``jaxstream.obs.trace.SPANS``, this process): ``serve.boundary`` in a
serving run (health fetch's end to the next dispatch: finalize, refill,
observers), else ``sim.boundary`` (``Simulation``: one segment's sync
end to the next dispatch, across ``run()`` calls).  The table covers
the whole run, not only the traced window; a compiling segment's
boundary is left out, and the median absorbs the set-up and drain
boundaries.  None where the program keeps no such table.  Moves
``sim_days_per_s``."""


def read(layer):
    from jaxstream.obs import trace

    table = getattr(trace, "SPANS", None)
    if table is None:
        return None
    spans = table.summary()["spans"]
    for name in ("serve.boundary", "sim.boundary"):
        s = spans.get(name)
        if s and s["count"]:
            return 1e3 * s["median_s"]
    return None
