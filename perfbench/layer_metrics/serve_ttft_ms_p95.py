"""Per-layer metric ``serve_ttft_ms_p95``."""


def read(record):
    """95th percentile of ``submit`` -> first streamed token on the
    benchmark's clock, over every request submitted inside the window; one
    still waiting for its first token when the window closes counts with the
    wait it has had (a lower bound), so that the slowest are not left out."""
    from perfbench.harness import percentile
    return percentile(record.get("ttft_ms") or [], 95)
