"""SMS: seconds in `sms.store` spans (slab writes, chunk map, writeback
hand-off and insertion-log append of a PUT batch's chunks) per GiB of PUT
payload acked in the window."""

from bench.harness import spans


def read(run):
    return spans.site_per_gib(run, "sms.store")
