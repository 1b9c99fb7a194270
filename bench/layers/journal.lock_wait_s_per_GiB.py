"""Journal: seconds the PUT path waited for the spill journal's lock
(`journal.lock_wait` spans in appends and syncs) per GiB of PUT payload
acked in the window."""

from bench.harness import spans


def read(run):
    return spans.site_per_gib(run, "journal.lock_wait")
