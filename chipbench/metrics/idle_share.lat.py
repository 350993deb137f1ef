"""Device: share of the traced stretch in which no operation ran, mean over
the chips, in percent."""

from chipbench import readings as R


def read(run):
    return R.idle_percent(run)
