"""What the loop's thread does or waits for per frame that is neither the
device's programs nor the producer (host clock): the iteration, `window_s /
frames`, less the thread's `fetch.ready` (the wait for the frame's device
programs) and `ingest.wait` (the wait for a field to land; only the shm
source opens it). Beside `sim_device_ms` + `step_device_ms` it says who
paces: where it is the larger, the host does. Nothing from a program whose
spans carry no `thread`."""

NAME = "host_serial_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "entry"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from chipbench import loop_spans

    spans = loop_spans.loop(ctx)
    ready = loop_spans.named_ms(ctx, "fetch.ready")
    if ready is None:
        return None
    waits = [e for e in spans if e["name"] == "ingest.wait"]
    return (loop_spans.interval_ms(ctx) - ready
            - loop_spans.per_frame_ms(ctx, waits))
