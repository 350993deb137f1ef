"""Engine slots: generated tokens kept over slot-steps run, in percent.  A
wave runs as many steps as its longest request and always all ``batch``
slots, so short requests and partial waves leave slots idle."""


def read(run):
    calls = run.record["calls"]
    kept = sum(r["n_out"] for r in run.record["requests"] if r["ok"])
    steps = sum(c["steps"] for c in calls) * run.record["batch"]
    return 100.0 * kept / steps if steps else None
