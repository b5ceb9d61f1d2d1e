"""What the `ingest` readers share: the window's `ingest.upload` spans (the
shm source's uploader thread: slot pinned -> transfer landed) as bytes and
seconds. The bytes are the spans' own `bytes` attribute, so a reader needs
nothing but its `ctx`. (None, None) from a run that recorded no such span,
as a program from before the spans does not."""


def window(ctx) -> tuple:
    """(bytes put on the device, seconds inside the spans)."""
    ups = [e for e in ctx["spans"] if e["name"] == "ingest.upload"]
    if not ups:
        return None, None
    return (sum(e["attrs"]["bytes"] for e in ups),
            sum(e["dur"] for e in ups))
