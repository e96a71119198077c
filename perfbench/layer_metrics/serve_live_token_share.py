"""Per-layer metric ``serve_live_token_share``."""


def read(record):
    """Share of the ragged engine step's token budget that held a token:
    sum of ``live_tokens`` over sum of ``token_budget`` of the traced
    ``ds:serve.step`` spans of kind ``ragged`` (the batch builder's counts)."""
    from perfbench import program_trace
    s = program_trace.summary(record)
    sums = s["serve"]["ragged_sums"] if s else {}
    if not sums.get("token_budget"):
        return None
    return 100.0 * sums["live_tokens"] / sums["token_budget"]
