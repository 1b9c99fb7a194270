"""Codec: seconds in `ec.collect` spans (waiting for the RS kernel's
results, their device-to-host transfer and the copy into the parity
buffer) per GiB of PUT payload acked in the window."""

from bench.harness import spans


def read(run):
    return spans.site_per_gib(run, "ec.collect")
