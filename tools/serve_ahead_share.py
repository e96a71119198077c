"""The share of serving steps that were launched ahead, from a profiler trace.

Every ``ds:serve.step`` span carries ``launched_ahead`` (1: the step before
it was still unfetched when it was launched, so the device had it queued when
that one ended; ``serving/scheduler.py``).  This reads the newest trace of a
``--trace 1`` benchmark run (``.perfbench_trace/`` under the working directory), or the ``.xplane.pb``
given, and prints the share with the host's turn beside it:

    python3 tools/serve_ahead_share.py [trace.xplane.pb]
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from deepspeed_tpu.telemetry import names  # noqa: E402
from perfbench import program_trace  # noqa: E402


def ahead_share(path):
    planes = program_trace.read_file(path)
    host = [e for line in planes.get(program_trace.HOST_PLANE, {}).values()
            for e in line]
    prefix = names.SPAN_PREFIX
    steps = [e for e in host if e[0] == prefix + names.SERVE_STEP]
    fetches = [e for e in host if e[0] == prefix + names.SERVE_FETCH]
    if not steps:
        return None
    known = [e for e in steps if "launched_ahead" in e[3]]
    ahead = sum(int(e[3]["launched_ahead"]) for e in known)
    inside = [sum(s[1] <= f[1] and f[2] <= s[2] for f in fetches)
              for s in steps]
    waited = sum(f[2] - f[1] for f in fetches) / 1e6
    return {"steps": len(steps), "with_the_count": len(known),
            "launched_ahead": ahead,
            "share": ahead / len(known) if known else None,
            "fetches_a_step": sorted(set(inside)),
            "turn_ms_mean": sum(e[2] - e[1] for e in steps) / 1e6 / len(steps),
            "fetch_wait_ms_mean": waited / len(steps)}


if __name__ == "__main__":
    trace = sys.argv[1] if len(sys.argv) > 1 else \
        program_trace.find_trace(os.getcwd())
    if trace is None:
        raise SystemExit("no trace under .perfbench_trace/: run a cell with "
                         "--trace 1 first")
    print(json.dumps({"trace": os.path.relpath(trace), **ahead_share(trace)}))
