"""Engine: 90th percentile of the gap between successive tokens of a
request in host hands, over every gap of the window's requests
(``_engine_window``; the engine's recorder).  A failed request adds one
infinite gap."""

import math

import numpy as np

from chipbench import readings as R
from chipbench.metrics import _engine_window as E


def read(run):
    w = E.window(run)
    if w is None:
        return None
    gaps = []
    for uid in w.uids:
        if run.record["requests"][uid]["ok"]:
            gaps.extend(np.diff(w.requests[uid]["token_ns"]) * 1e-9)
        else:
            gaps.append(math.inf)
    return R.percentile(gaps, 90)
