"""Engine queue: 90th percentile of due time to the start of the
``Engine.generate`` call that served the request (host clock)."""

from chipbench import readings as R


def read(run):
    return R.percentile(R.queue_waits(run), 90)
