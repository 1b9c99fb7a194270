"""PUT path: seconds of `daemon.put_many` spans covered by none of their
children on the daemon thread (time no stage names) per GiB of PUT
payload acked in the window."""

from bench.harness import spans


def read(run):
    return spans.per_gib(spans.self_seconds(run.spans, "daemon.put_many"),
                         run.put_bytes)
