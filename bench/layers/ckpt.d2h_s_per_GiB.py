"""Checkpoint: seconds in `ckpt.d2h` spans (each leaf's device-to-host
copy in `Checkpointer.save`) per GiB of PUT payload acked in the
window."""

from bench.harness import spans


def read(run):
    return spans.site_per_gib(run, "ckpt.d2h")
