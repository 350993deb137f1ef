"""The family's attention kernel: the least time its calls in the traced
stretch could take (the longer of operations at peak and bytes at peak
bandwidth, from the family's ``kernel_cost``) over the device time of its
events, in percent.  Each event is one layer of one prefill call: ``batch``
slots of ``prompt_len`` tokens."""

from chipbench import flops as F


def read(run):
    t, rec = run.trace, run.record
    if t is None or run.peak is None or not t["kernel_events"] or t["kernel_s"] <= 0:
        return None
    ops, nbytes = run.family.kernel_cost(run.model, batch=rec["batch"], seq=rec["prompt_len"])
    return 100.0 * t["kernel_events"] * F.least_time_s(ops, nbytes, run.peak) / t["kernel_s"]
