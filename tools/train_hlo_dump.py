"""Write the optimised HLO of a training cell's micro-step program, with what
only names its source taken out (chip only; ~1-3 min a cell).

``python tools/train_hlo_dump.py --workload mistral7b_train_4k --out DIR``
builds the cell's engine as ``perfbench/jobs/train.py`` does, runs one
``engine(ids, ids)`` (the one compile of ``ds_micro_<variant>``) and writes
``DIR/<workload>.<program>.hlo.txt`` through ``serve_hlo_check.comparable``:
no ``metadata={...}``, no tables of files and lines, no Mosaic bytecode.  Run
it in a ``git archive`` of the parent (copy this file into its ``tools/``) and
in the tree, then ``diff -r`` the two directories: a change that only renames
(``jax.named_scope``) leaves the diff empty.  ``--root`` and ``--cpu`` take
the tests' tiny root off the chip.
"""

import argparse
import os
import re
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def dump(workload, out_dir, root, seed=0):
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
        written.append((path, len(text.split("\n")),
                        {s: raw.count(s + "/") for s in sorted(set(
                            re.findall(r"\bds\.\w+", raw)))}))
    return written


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--root", default=None,
                    help="a root with its own BENCHMARK.json (the tests')")
    ap.add_argument("--cpu", action="store_true",
                    help="do not refuse a CPU (tiny roots only)")
    args = ap.parse_args()
    import jax
    from perfbench import loader
    if jax.devices()[0].platform != "tpu" and not args.cpu:
        sys.exit("train_hlo_dump reads the chip's compiler: no TPU here")
    for path, lines, scopes in dump(args.workload, args.out,
                                    args.root or loader.ROOT):
        print(f"{path}: {lines} lines; instructions under each scope "
              f"(metadata, left out of the file): {scopes}")


if __name__ == "__main__":
    main()
