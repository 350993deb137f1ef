"""Mean request latency over every request of the window: due time to the
return of the call that served it."""

from chipbench import readings as R


def read(run):
    return R.mean(R.latencies(run))
