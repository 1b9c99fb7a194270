"""Codec: seconds in `ec.frame` spans (zeroing the stacked (k, L) encode
buffer and copying the framed payloads into it) per GiB of PUT payload
acked in the window."""

from bench.harness import spans


def read(run):
    return spans.site_per_gib(run, "ec.frame")
