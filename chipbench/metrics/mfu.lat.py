"""Jitted steps: operations of the requests served (the family's
``sequence_flops``) over the window's span, the chips and their peak, in
percent."""

from chipbench import readings as R


def read(run):
    return R.mfu_percent(run)
