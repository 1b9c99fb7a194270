"""Journal: seconds in `journal.write` spans (the write() calls of each
frame, on whichever thread appends it) per GiB of PUT payload acked in
the window."""

from bench.harness import spans


def read(run):
    return spans.site_per_gib(run, "journal.write")
