"""Per-layer metric ``serve_state_bytes_per_seq``."""


def read(record):
    """Bytes of recurrent state the cache holds for ONE sequence, over all
    the state-space layers (a row a sequence slot, fixed whatever the
    context): the ``state_row_bytes`` of the traced ``ds:serve.step`` spans
    (the engine's own count of its buffers).  None where no step carries it
    (a model with no state-space layer, a parent before PR 41)."""
    from perfbench import serve_trace
    t = serve_trace.traced(record)
    rows = [int(c["state_row_bytes"]) for c in (t["steps"] if t else ())
            if "state_row_bytes" in c]
    return sum(rows) / len(rows) if rows else None
