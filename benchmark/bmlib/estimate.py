"""From the delivery log to the end-to-end numbers.

``events_per_s`` is the plain rate: every event completed between the
window's first delivery and its last, over the time between them. A
stall inside the window costs what it took. Beside it, on the earlier
``[bench] window`` line and never reported, stand the slices (runs of
consecutive deliveries spanning at least ``min_s`` seconds), their
median and quartiles: a stalled run shows there as one slow slice, and
the median says what the run would have read without it.
"""

from __future__ import annotations

import statistics


def slices(t, e, min_s=1.0):
    """[(seconds, events)] from delivery times ``t`` and the newest event
    index ``e`` at each. The tail shorter than ``min_s`` is left out."""
    out = []
    i = 0
    for j in range(1, len(t)):
        if t[j] - t[i] >= min_s:
            out.append((t[j] - t[i], e[j] - e[i]))
            i = j
    return out


def rate_summary(t, e, min_s=1.0):
    """The plain rate, and the slices printed beside it."""
    sl = slices(t, e, min_s)
    rates = [de / dt for dt, de in sl]
    out = {
        "deliveries": len(t),
        "slices": len(sl),
        "plain_rate": (e[-1] - e[0]) / (t[-1] - t[0]) if len(t) > 1 else None,
        "window_s": t[-1] - t[0] if t else 0.0,
        "slice_rates": rates,
    }
    if rates:
        out["median"] = statistics.median(rates)
        if len(rates) >= 2:
            q = statistics.quantiles(rates, n=4)
            out["q1"], out["q3"] = q[0], q[2]
        out["min"], out["max"] = min(rates), max(rates)
    return out


def percentile(sorted_values, q):
    """Nearest-rank percentile of an ascending sequence."""
    n = len(sorted_values)
    if not n:
        return None
    k = min(n - 1, max(0, int(-(-q * n // 100)) - 1))
    return float(sorted_values[k])
