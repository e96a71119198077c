"""perfbench — the repo's on-chip yardstick (see README.md, ../PERF.md)."""
