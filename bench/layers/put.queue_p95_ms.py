"""PUT path: the 95th percentile, in ms, of the time PUTs waited in the
client daemon's queue (`daemon.queue` spans tagged `op=put`, from the
submit to the task's start on the daemon), by the rule `put_p95_ms`
takes over the window's raw samples."""

from bench.harness import spans, stats


def read(run):
    waits = [s["dur_us"] / 1e3 for s in spans.of(run.spans, "daemon.queue")
             if s["tags"].get("op") == "put" and s["dur_us"] is not None]
    return stats.percentile(waits, 95) if waits else None
