"""Engine host work in decode: the part of the window's ``engine.step``
spans outside their ``engine.wait`` (the wait for the step's sampled
tokens), in percent.  It is host time, not device idle time: the host
dispatches the step and samples, while the device may still run, and
reads the tokens after the wait."""

from chipbench.metrics import _engine_window as E


def read(run):
    w = E.window(run)
    if w is None:
        return None
    steps = w.named("engine.step")
    waits = w.children("engine.wait")
    total = sum(E.seconds(s) for s in steps)
    if total <= 0 or any(s.seq not in waits for s in steps):
        return None
    return 100.0 * sum(E.seconds(s) - E.seconds(waits[s.seq]) for s in steps) / total
