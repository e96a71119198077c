"""Write the optimised HLO of a training cell's micro-step program, with what
only names its source taken out (chip only; ~1-3 min a cell).

``python tools/train_hlo_dump.py --workload mistral7b_train_4k --out DIR``
builds the cell's engine as ``perfbench/jobs/train.py`` does, runs one
``engine(ids, ids)`` (the one compile of ``ds_micro_<variant>``) and writes
``DIR/<workload>.<program>.hlo.txt`` through ``serve_hlo_check.comparable``:
no ``metadata={...}``, no tables of files and lines, no Mosaic bytecode.  Run
it in a ``git archive`` of the parent (copy this file into its ``tools/``) and
in the tree, then ``diff -r`` the two directories: a change that only renames
(``jax.named_scope``) leaves the diff empty.  Beside each ``ds.*`` scope's
instruction count it prints how many ops of their own (outside every fusion:
what a trace shows as an op) are ``dynamic-update-slice`` and how many are
collectives: a product the compiler emits in pieces and writes into place
shows here (PR 57: 64 under ``ds.attn_proj`` on four chips, none on one).
``--root`` and ``--cpu`` take the tests' tiny root off the chip.
"""

import argparse
import gzip
import os
import re
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


#: a collective as the optimised HLO spells it; an async pair counts once
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "all-reduce-scatter")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?[\w.\-]+ = .*?\s([a-z][\w\-]*)\(")


def scope_counts(raw):
    """``{scope: {"instructions", "dynamic_update_slice", "collectives"}}``
    of the raw optimised HLO: every mention of ``ds.<scope>/`` (as PR 56
    counted); the ``dynamic-update-slice`` instructions OUTSIDE every
    fusion's body (ops of their own on the device: one fused into a product
    writes in place and costs nothing); and the collectives wherever they
    stand (``-start`` once, ``-done`` not)."""
    out = {s: {"instructions": raw.count(s + "/"), "dynamic_update_slice": 0,
               "collectives": 0}
           for s in sorted(set(re.findall(r"\bds\.\w+", raw)))}
    bodies = set(re.findall(r"\sfusion\(.*?calls=%([\w.\-]+)", raw))
    fused = False
    for line in raw.split("\n"):
        if line.endswith("{") and not line.startswith(" "):
            fused = line.split(" (", 1)[0].split()[-1].lstrip("%") in bodies
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        op = m.group(1)
        if op == "dynamic-update-slice" and not fused:
            kind = "dynamic_update_slice"
        elif op.removesuffix("-start") in COLLECTIVES:
            kind = "collectives"
        else:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        for s in set(re.findall(r"\bds\.\w+(?=/)", name.group(1))
                     if name else ()):
            out[s][kind] += 1
    return out


def dump(workload, out_dir, root, seed=0, keep_raw=False):
    import jax
    import numpy as np
    from perfbench import loader
    from perfbench.harness import fold_seed
    from serve_hlo_check import comparable

    # the executable's own text: one loaded from a cache may carry none
    jax.config.update("jax_enable_compilation_cache", False)
    manifest = loader.load_manifest(root)
    cell = loader.find(manifest["workloads"], workload, "workload")
    entry = loader.find(manifest["configs"], cell["config"], "config")
    config = loader.load_json(os.path.join(root, entry["file"]))
    traffic = loader.load_json(
        loader.part_path(root, "traffic", cell["traffic"], "json"))
    job = loader.load_part(root, "jobs", traffic["job"])
    arch = loader.load_part(root, "models", config["arch"])
    devices = jax.devices()[:cell["chips"]]
    ctx = types.SimpleNamespace(traffic=traffic, devices=devices)
    model, tp_rules = arch.build(config, "train")
    vocab = arch.reference_sizes(config, "train")["vocab_size"]
    rows = traffic["micro_batch_per_chip"] * len(devices)
    ids = np.random.default_rng(seed).integers(
        0, vocab, size=(rows, traffic["seq_len"])).astype(np.int32)
    engine = job._build_engine(ctx, model, tp_rules, fold_seed(seed), ids)
    jax.block_until_ready(engine(ids, ids))
    os.makedirs(out_dir, exist_ok=True)
    written = []
    for n, compiled in enumerate(engine._compiled_micro.values()):
        name = f"ds_micro_{engine._micro_variant()}" + (f".{n}" if n else "")
        path = os.path.join(out_dir, f"{workload}.{name}.hlo.txt")
        raw = compiled.as_text()
        text = comparable(raw)
        with open(path, "w") as f:
            f.write(text)
        # what the comparison leaves out is there: the scopes in the raw text
        if keep_raw:
            with gzip.open(path[:-len("hlo.txt")] + "raw.hlo.txt.gz",
                           "wt") as f:
                f.write(raw)
        written.append((path, len(text.split("\n")), scope_counts(raw)))
    return written


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--root", default=None,
                    help="a root with its own BENCHMARK.json (the tests')")
    ap.add_argument("--cpu", action="store_true",
                    help="do not refuse a CPU (tiny roots only)")
    ap.add_argument("--raw", action="store_true",
                    help="also write the raw text (gzip, with the metadata "
                         "that names each op's scope) beside the file")
    args = ap.parse_args()
    import jax
    from perfbench import loader
    if jax.devices()[0].platform != "tpu" and not args.cpu:
        sys.exit("train_hlo_dump reads the chip's compiler: no TPU here")
    for path, lines, scopes in dump(args.workload, args.out,
                                    args.root or loader.ROOT,
                                    keep_raw=args.raw):
        print(f"{path}: {lines} lines; under each scope (metadata, left out "
              "of the file) instructions / dynamic-update-slice ops / "
              "collectives: " + ", ".join(
                  f"{s} {c['instructions']} / {c['dynamic_update_slice']} / "
                  f"{c['collectives']}" for s, c in scopes.items()))


if __name__ == "__main__":
    main()
