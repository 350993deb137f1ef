"""90th-percentile request latency: due time to the return of the call
that served it."""

from chipbench import readings as R


def read(run):
    return R.percentile(R.latencies(run), 90)
