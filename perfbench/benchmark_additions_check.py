#!/usr/bin/env python3
"""``python3 perfbench/benchmark_additions_check.py <base-ref> [<head-ref>]``
— does the head only ADD to the benchmark?  Run it from inside the repository
as the last step of any PR that is not of kind ``benchmark``: the driver
refuses such a PR (``benchmark_edited``) for ONE touched file, whatever else
it brings (PR 31 lost eleven new files to it).

It lists, against ``<base-ref>`` (``<head-ref>`` absent: the working tree),

* every file under ``BENCHMARK.json``'s ``paths`` that the head modifies,
  renames or deletes (added files are fine), and
* comparing the two PARSED manifests (re-wrapped lines and moved commas are
  no change), every change to ``BENCHMARK.json`` that is not an entry appended
  to ``configs`` / ``workloads`` / ``per_layer`` or a cell's name appended to a
  metric's ``workloads``,

and exits 1 if there is any, 0 if the head reads clean.  Plain git and the
standard library; it imports nothing of the benchmark or the program.
"""

import json
import subprocess
import sys

MANIFEST = "BENCHMARK.json"
#: sections a later PR may append entries to
APPENDABLE = ("configs", "workloads", "per_layer")
#: sections whose entries are metrics: a cell's name may be appended to the
#: ``workloads`` list of one that is there
METRICS = ("end_to_end", "per_layer")


def git(*args, cwd=None):
    return subprocess.run(["git", *args], cwd=cwd, check=True,
                          capture_output=True, text=True).stdout


def manifest_at(ref, root):
    """The parsed manifest of ``ref`` (None: the working tree's)."""
    if ref is None:
        with open(f"{root}/{MANIFEST}") as f:
            return json.load(f)
    return json.loads(git("show", f"{ref}:{MANIFEST}", cwd=root))


def touched_files(base, head, paths, root):
    """``[(status, path)]`` of the files under ``paths`` that the head does
    anything to but add."""
    refs = [base] + ([head] if head else [])
    out = git("diff", "--name-status", "-M", *refs, "--", *paths, cwd=root)
    found = []
    for line in out.splitlines():
        status, *names = line.split("\t")
        if status[0] in "AC":                 # added, or a copy of a file
            continue
        what = {"M": "modified", "D": "deleted", "R": "renamed",
                "T": "changed its type"}.get(status[0], status)
        found.append((what, " -> ".join(names)))
    return found


def entry_changes(section, old, new):
    """What differs between an entry that was there and the one in its
    place, beyond a cell's name appended to a metric's ``workloads``."""
    keys = sorted(k for k in set(old) | set(new) if old.get(k) != new.get(k))
    cells, now = old.get("workloads"), new.get("workloads")
    if section in METRICS and isinstance(cells, list) and \
            isinstance(now, list) and now[:len(cells)] == cells:
        keys = [k for k in keys if k != "workloads"]
    if not keys:
        return []
    return [f"{section}: entry {old.get('name')!r} changed in "
            f"{', '.join(keys)}: "
            + "; ".join(f"{k} {old.get(k)!r} -> {new.get(k)!r}"
                        for k in keys)]


def manifest_changes(old, new):
    """Every change from ``old`` to ``new`` that is more than an addition."""
    found = []
    for key in sorted(set(old) | set(new)):
        a, b = old.get(key), new.get(key)
        if not (isinstance(a, list) and isinstance(b, list)
                and key in APPENDABLE + METRICS):
            if a != b:
                found.append(f"{key}: {a!r} -> {b!r}")
            continue
        was, now = [[e.get("name") for e in x] for x in (a, b)]
        gone = [n for n in was if n not in now]
        kept = [n for n in was if n in now]
        added = [n for n in now if n not in was]
        if gone:
            found.append(f"{key}: entries removed: {gone}")
        if kept != now[:len(kept)]:
            found.append(f"{key}: the entries that were there moved, or a "
                         "new one stands before them (append at the end)")
        for n in kept:
            found += entry_changes(key, a[was.index(n)], b[now.index(n)])
        if added and key not in APPENDABLE:
            found.append(f"{key}: entries added: {added} (only a "
                         "`benchmark` PR adds an end-to-end metric)")
    return found


def check(base, head=None, root=None):
    """``(findings, summary)``: what the head changes of the benchmark that
    was there at ``base``, and one line on what it adds."""
    root = root or git("rev-parse", "--show-toplevel").strip()
    old, new = manifest_at(base, root), manifest_at(head, root)
    findings = [f"{path}: {what}"
                for what, path in touched_files(base, head, old["paths"],
                                                root)]
    findings += [f"{MANIFEST}: {c}" for c in manifest_changes(old, new)]
    added = {k: len(new.get(k, [])) - len(old.get(k, [])) for k in APPENDABLE}
    return findings, ", ".join(f"{n} {k}" for k, n in added.items())


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if not 1 <= len(argv) <= 2:
        print(__doc__, file=sys.stderr)
        return 2
    findings, added = check(*argv)
    for f in findings:
        print(f)
    against = argv[1] if len(argv) == 2 else "the working tree"
    if findings:
        print(f"{len(findings)} change(s) to the benchmark that {argv[0]} "
              f"had, in {against}: a PR that is not of kind `benchmark` is "
              "refused for any of them")
        return 1
    print(f"clean: {against} only adds to the benchmark of {argv[0]} "
          f"(entries appended: {added})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
