"""Per-layer metric ``serve_loop_passes_per_row``: the passes of a looped
model's stack that a live row ran, on average: ``total_ut_steps`` while no
row leaves the loop early, and the number an adaptive exit would move."""

COUNT = "loop_row_passes"


def read(record):
    """``loop_row_passes`` over the live rows it counts.  The count is made
    on the device and comes back on the fetch of a step that a request waits
    for, together with the counts of the ``launches_covered - 1`` launches
    before it that fetched nothing; so the rows under a fetch's count are the
    ``live_tokens`` of exactly the launches it covers, and a fetch counts
    only where all of them are whole rows of the step table
    (``perfbench/step_trace.py``): the quotient has no edge.  None for an
    untraced run or a trace in which no fetch carries the count."""
    from perfbench import step_trace
    t = step_trace.traced(record)
    if not t:
        return None
    live = {r["launch"]: r["counts"].get("live_tokens") for r in t["rows"]}
    passes = rows = 0
    for r in t["rows"]:
        counted = r["device_counts"]
        if COUNT not in counted:
            continue
        covered = range(
            r["launch"] - int(counted.get("launches_covered", 1)) + 1,
            r["launch"] + 1)
        if all(live.get(n) is not None for n in covered):
            passes += int(counted[COUNT])
            rows += sum(int(live[n]) for n in covered)
    return passes / rows if rows else None
