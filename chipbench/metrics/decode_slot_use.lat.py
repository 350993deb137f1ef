"""Engine slots in decode: slots that kept a token over slots run, summed
over the window's ``engine.step`` spans (their ``live`` over ``batch``), in
percent.  Unlike ``slot_use.lat`` it leaves out the prefill's token."""

from chipbench.metrics import _engine_window as E


def read(run):
    w = E.window(run)
    steps = w.named("engine.step") if w is not None else []
    if not steps:
        return None
    return 100.0 * sum(s.ids["live"] for s in steps) / (len(steps) * run.record["batch"])
