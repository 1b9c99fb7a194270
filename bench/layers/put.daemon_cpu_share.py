"""PUT path: the daemon thread's CPU time inside `daemon.put_many` spans
over their wall time, in percent: below 100 the daemon waits (on the
disk, a lock, the GIL or the device) rather than computes."""

from bench.harness import spans


def read(run):
    return spans.cpu_share(run.spans, "daemon.put_many")
