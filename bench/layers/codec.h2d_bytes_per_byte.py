"""Codec: bytes sent to the device (the `bytes` tags of `ec.dispatch`
spans: padded column tiles of the data rows) per byte of PUT payload
acked in the window."""

from bench.harness import spans


def read(run):
    sent = spans.tag_sum(spans.of(run.spans, "ec.dispatch"), "bytes")
    if sent is None or not run.put_bytes:
        return None
    return sent / run.put_bytes
