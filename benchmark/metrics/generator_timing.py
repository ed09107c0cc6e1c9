"""How the open-loop generator kept its schedule, in ms.

``late_p95``: 95th percentile of a batch's release time minus the time
its last event was due. ``batch_wait_mean``: mean over events of release
time minus creation time (an event waits for its batch to fill, about
half a period, and then for the job to poll)."""

import numpy as np


def read(ctx, what):
    src = ctx.source
    rel = np.asarray(getattr(src, "released_at", ()), float)
    if not len(rel):
        return None
    due_last = src.t0 + (np.arange(len(rel)) + 1) * src.period
    keep = (rel >= ctx.snap0["t"]) & (rel <= ctx.snap1["t"])
    if not keep.any():
        return None
    late = (rel - due_last)[keep] * 1e3
    if what == "late_p95":
        return float(np.percentile(late, 95))
    return float(np.mean(late) + src.period * 1e3 / 2)
