"""Engine: 90th percentile of time to first token over the window's
requests (``_engine_window``): due time to the start of the
``Engine.generate`` call that served the request (the driver's record),
plus that call's start to the request's first token in host hands (the
engine's recorder)."""

import math

from chipbench import readings as R
from chipbench.metrics import _engine_window as E


def read(run):
    w = E.window(run)
    if w is None:
        return None
    ttft = []
    for uid in w.uids:
        r = run.record["requests"][uid]
        if r["ok"]:
            rec = w.requests[uid]
            ttft.append(r["start"] - r["due"] + (rec["token_ns"][0] - rec["start_ns"]) * 1e-9)
        else:
            ttft.append(math.inf)
    return R.percentile(ttft, 90)
