"""Engine reads in decode: the window's ``engine.read`` spans inside its
``engine.step`` spans (the host reading each live slot's token after the
wait, and the slots' bookkeeping) over those steps, in percent.  The part
of ``decode_host_share.lat`` that reading the tokens in one transfer
would cut."""

from chipbench.metrics import _engine_window as E


def read(run):
    w = E.window(run)
    if w is None:
        return None
    steps = w.named("engine.step")
    reads = w.children("engine.read")
    total = sum(E.seconds(s) for s in steps)
    if total <= 0 or any(s.seq not in reads for s in steps):
        return None
    return 100.0 * sum(E.seconds(reads[s.seq]) for s in steps) / total
