"""Checkpoint: seconds `Checkpointer.save` waited on its PUT batches
(`ckpt.wait` spans: the bound on batches in flight and the final
barrier) per GiB of PUT payload acked in the window."""

from bench.harness import spans


def read(run):
    return spans.site_per_gib(run, "ckpt.wait")
