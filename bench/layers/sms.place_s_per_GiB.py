"""SMS: seconds in `sms.place` spans (chunk placement, and the copy of
each chunk out of the encode buffer in a batch of several fragments) per
GiB of PUT payload acked in the window."""

from bench.harness import spans


def read(run):
    return spans.site_per_gib(run, "sms.place")
