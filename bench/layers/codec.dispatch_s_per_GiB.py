"""Codec: seconds in `ec.dispatch` spans (the RS kernel wrapper's padded
tile copies, the host-to-device transfers and the kernel launches) per
GiB of PUT payload acked in the window."""

from bench.harness import spans


def read(run):
    return spans.site_per_gib(run, "ec.dispatch")
