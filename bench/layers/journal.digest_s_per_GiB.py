"""Journal: seconds in `journal.digest` spans (each frame's payload
digest and CRC) per GiB of PUT payload acked in the window."""

from bench.harness import spans


def read(run):
    return spans.site_per_gib(run, "journal.digest")
