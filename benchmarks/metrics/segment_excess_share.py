"""Share of the forecast's segment wall time spent beyond the median
segment, in percent: 100 x sum(max(0, w - median w)) / sum(w) over the
``sim.segment`` spans (dispatch start to sync end) of the run's most
common step count, from the program's own span table
(``jaxstream.obs.trace.SPANS``, this process; compiling segments are
not in it).  A run whose segments all take the median reads 0; one
held segment shows as its excess.  The table covers the whole run, not
only the traced window.  None where the program keeps no such table.
Moves ``sim_days_per_s``."""

import collections
import statistics


def read(layer):
    from jaxstream.obs import trace

    table = getattr(trace, "SPANS", None)
    if table is None:
        return None
    seg = table.summary()["spans"].get("sim.segment")
    if not seg or not seg["recent_s"]:
        return None
    steps = [(a or {}).get("steps") for a in seg["recent_attrs"]]
    common = collections.Counter(steps).most_common(1)[0][0]
    w = [d for d, k in zip(seg["recent_s"], steps) if k == common]
    med = statistics.median(w)
    total = sum(w)
    if total <= 0:
        return None
    return 100.0 * sum(max(0.0, x - med) for x in w) / total
