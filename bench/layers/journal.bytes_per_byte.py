"""Journal: bytes the spill journal wrote (the `bytes` tags of
`journal.write` frames and `journal.compact` copies) per byte of PUT
payload acked in the window."""

from bench.harness import spans


def read(run):
    wrote = spans.tag_sum(spans.of(run.spans, "journal.write"), "bytes")
    if wrote is None or not run.put_bytes:
        return None
    moved = spans.tag_sum(spans.of(run.spans, "journal.compact"), "bytes")
    return (wrote + (moved or 0)) / run.put_bytes
