#!/usr/bin/env python
"""Does a serving step program touch a layer's cache pages only in place?

Compiles, from shapes alone (nothing is allocated), the ragged step of a
perfbench configuration's architecture and ``decode_burst`` at the
configuration's serving layout, and reads the OPTIMISED HLO:

* every cache buffer (each layer's K pages and V pages) is in the module's
  ``input_output_alias``;
* no instruction outside a fusion's body — a ``copy``, a slice, an update, a
  fusion — gives a value of the size of a layer's K pages or larger, except
  the scatter that writes a step's K/V rows into the donated buffer (its
  output IS that buffer); the same inside the ``while`` body of the burst;
* for a cache with recurrent state rows (``ragged.py``: a row a sequence slot,
  e.g. ``perfbench/configs/jamba2_3b_1chip.json``), no ``copy`` gives a value
  of a state buffer's shape, in the step or in the burst's ``while`` body: the
  rows are read and written where they lie.

``python tools/serve_hlo_check.py [--aot] [--dump DIR] [configuration
files]``: on the attached device, or with ``--aot`` for a described ``TPU v5
lite`` with no chip (compile only; JAX itself stays on the CPU).  One JSON line
a program, exit 1 if a program copies.  It says what the compiler planned, not
how long it takes.

``--dump DIR`` also writes each program's optimised HLO there, with what
names the source taken out (:func:`comparable`): run it in two checkouts and
``diff -r`` the directories to see whether a change moved another
configuration's programs at all (PR 41: the four older serving configurations'
eight programs came out identical).
"""

import argparse
import json
import math
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

if "--aot" in sys.argv:
    # compile for a TPU while this process's backend is the CPU: the kernel
    # modules read the flag when they are imported (tools/aot_kernel_check.py)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["DS_ACCELERATOR"] = "cpu"
    os.environ["DS_TPU_PALLAS_INTERPRET"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

DEFAULT_CONFIGS = ("perfbench/configs/mistral7b_1chip.json",
                   "perfbench/configs/evabyte_1chip.json",
                   "perfbench/configs/command_a_plus_1chip.json",
                   "perfbench/configs/pangu_ultra_moe_1chip.json",
                   "perfbench/configs/jamba2_3b_1chip.json")

# name = shape opcode(...: a tuple shape has spaces, no " word(" inside it
_INSTR = re.compile(r"^\s*(?:ROOT )?(%?[\w.\-]+) = (.+?) ([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
          "f8e4m3fn": 1, "pred": 1, "s64": 8, "u64": 8, "f64": 8}


def _largest(shape_text):
    """Bytes of the largest array in an instruction's result shape."""
    best = 0
    for dt, dims in _SHAPE.findall(shape_text):
        n = _BYTES.get(dt, 0)
        for d in dims.split(","):
            n *= int(d) if d else 1
        best = max(best, n)
    return best


def page_sized_values(hlo_text, page_bytes):
    """``[(computation, opcode, result shape, instruction name)]`` of every
    instruction that is not inside a fusion's body and whose result holds an
    array of ``page_bytes`` or more.  Parameters, tuples and their elements,
    ``while`` / ``call`` / ``conditional`` results and bitcasts name a buffer
    and move nothing: left out."""
    moves_nothing = {"parameter", "tuple", "get-tuple-element", "while",
                     "call", "conditional", "bitcast", "optimization-barrier"}
    out, comp = [], None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?(%?[\w.\-]+) (?:\(|\{)", line)
        if head and line.rstrip().endswith("{"):
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m or comp is None or "fused_computation" in comp:
            continue
        name, shape, opcode = m.groups()
        if opcode in moves_nothing or _largest(shape) < page_bytes:
            continue
        out.append((comp, opcode, shape, name))
    return out


def in_place_scatter(hlo_text, name):
    """Is the fusion ``name`` a scatter into its own operand?  (Its fused
    computation's root is a ``scatter``; XLA aliases a scatter's result with
    the operand it updates.)"""
    m = re.search(re.escape(name) + r" = .*calls=(%?[\w.\-]+)", hlo_text)
    if not m:
        return False
    body = re.search(r"^" + re.escape(m.group(1)) + r" .*?^\}", hlo_text,
                     re.S | re.M)
    return bool(body and re.search(r"ROOT \S+ = \S+ scatter\(", body.group(0)))


def comparable(hlo_text):
    """The optimised HLO without what only names its SOURCE: the tables of
    files and lines before the first computation, each instruction's
    ``metadata`` and stack frame, and the kernels' serialized Mosaic bodies
    (bytecode that carries line numbers; ``jax.make_jaxpr`` of the kernel's
    wrapper compares those).  Two checkouts whose programs are the same give
    the same text."""
    out, table = [], False
    for line in hlo_text.split("\n"):
        if line.strip() in ("FileNames", "FunctionNames", "FileLocations",
                            "StackFrames"):
            table = True
        elif table:
            table = bool(line.strip())
        else:
            out.append(line)
    text = re.sub(r", metadata=\{[^}]*\}", "", "\n".join(out))
    text = re.sub(r"stack_frame_id=\d+", "", text).replace("\\", "")
    return re.sub(r"custom_call_config[^}]*body[^,}]*",
                  "custom_call_config<body>", text)


def aliased_parameters(hlo_text):
    """Parameter numbers in the module's ``input_output_alias`` (the
    ``HloModule`` line's ``{output index}: (parameter, {index}, kind)``)."""
    return {int(p) for p in re.findall(r"\}: \((\d+), \{",
                                       hlo_text.split("\n", 1)[0])}


def state_copies(hlo_text, state_shapes):
    """The ``copy`` instructions (outside fusions' bodies) whose result has
    the shape of a recurrent state buffer (``bf16[3,257,5120]``)."""
    return [f"{comp}: copy {shape}" for comp, op, shape, _ in
            page_sized_values(hlo_text, 1)
            if op == "copy" and shape.split("{")[0] in state_shapes]


def check(compiled, n_params, n_cache, page_bytes, state_shapes=()):
    """One program's verdict.  ``n_params`` flat parameters come before the
    ``n_cache`` cache buffers in the entry computation's signature."""
    text = compiled.as_text()
    copied = state_copies(text, state_shapes)
    aliased = aliased_parameters(text)
    cache = set(range(n_params, n_params + n_cache))
    moved = [v for v in page_sized_values(text, page_bytes)
             if not (v[1] == "scatter"
                     or (v[1] == "fusion" and in_place_scatter(text, v[3])))]
    ma = compiled.memory_analysis()
    return {"cache_buffers": n_cache,
            "aliased": len(cache & aliased),
            "page_sized_values_moved": len(moved),
            "moved": [f"{c}: {op} {shape}" for c, op, shape, _ in moved[:8]],
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "page_bytes": page_bytes,
            "state_buffers_copied": len(copied), "copied": copied[:8],
            "ok": cache <= aliased and not moved and not copied}


def programs(config, sharding=None):
    """``{program name: (lowered, flat params, cache buffers, page bytes)}``
    of the configuration's ragged step, its widest burst and its burst of
    ONE iteration (what a turn of decode rows alone runs where the least
    remainder is 1: ISSUE 55)."""
    from perfbench import loader
    from deepspeed_tpu.inference.v2.ragged import BlockedKVCache
    from deepspeed_tpu.inference.v2 import ragged_forward as rf
    arch = loader.load_part(ROOT, "models", config["arch"])
    model, _ = arch.build(config, "serve")
    cfg = model.config
    eng = config["program"]["serve"]["engine"]
    bs, budget = int(eng["block_size"]), int(eng["token_budget"])
    seqs = int(eng["max_concurrent"]) + 1
    eva = getattr(cfg, "attention_class", None) == "eva"
    recurrent = getattr(cfg, "recurrent_state", None)
    kinds = recurrent["kinds"] if recurrent else ("pages", )

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree.map(lambda s: sds(s.shape, jnp.bfloat16),
                          arch.param_shapes(model))
    cache = jax.eval_shape(lambda: BlockedKVCache(
        BlockedKVCache.entries_of(cfg), int(eng["num_blocks"]), bs,
        cfg.num_key_value_heads, getattr(cfg, "head_dim", 0),
        dtype=jnp.bfloat16, window_size=cfg.window_size if eva else 0,
        chunk_size=cfg.chunk_size if eva else 0,
        latent_dim=getattr(cfg, "kv_latent_dim", 0),
        recurrent=recurrent, max_seqs=seqs,
        entries_a_buffer=getattr(cfg, "kv_entries_a_buffer", 1)).layers)
    cache = jax.tree.map(lambda s: sds(s.shape, s.dtype), cache)
    maxb = 64                       # the block table's width moves no page
    i32 = lambda *shape: sds(shape, jnp.int32)
    step_fn = rf.RAGGED_FORWARDS[type(model).__name__]
    kw = dict(cfg=cfg, block_size=bs)
    # a step that counts on the device hands the burst the counts it owes
    counts = getattr(step_fn, "step_counts", ())
    burst_kw = dict(counts0=i32(len(counts))) if counts else {}
    n_params = len(jax.tree.leaves(params))
    n_cache = len(jax.tree.leaves(cache))
    page = cache[kinds.index("pages")][0]
    page_bytes = page.dtype.itemsize * math.prod(page.shape)
    hlo_type = {"bfloat16": "bf16", "float32": "f32"}
    state_shapes = {        # a state leaf in the type the cache holds it in
        f"{hlo_type[leaf.dtype.name]}[{','.join(map(str, leaf.shape))}]"
        for entry, kind in zip(cache, kinds) if kind == "state"
        for leaf in entry}
    step = step_fn.lower(params, cache, i32(budget), i32(budget), i32(budget),
                         i32(seqs, maxb), i32(seqs), **kw)
    burst, burst_of_one = (rf.decode_burst.lower(
        params, cache, i32(seqs), i32(seqs), sds((seqs, ), jnp.bool_),
        i32(seqs, maxb), step_fn=step_fn, k=k, **kw, **burst_kw)
        for k in (int(eng["decode_burst"]), 1))
    rest = (n_params, n_cache, page_bytes, state_shapes)
    return {step_fn.__name__: (step, ) + rest,
            rf.decode_burst.__name__: (burst, ) + rest,
            rf.decode_burst.__name__ + "[k=1]": (burst_of_one, ) + rest}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("configs", nargs="*", default=DEFAULT_CONFIGS)
    ap.add_argument("--aot", action="store_true",
                    help="compile for a described TPU v5 lite, no chip")
    ap.add_argument("--dump", metavar="DIR",
                    help="write each program's comparable HLO there")
    args = ap.parse_args()
    sharding, kind = None, jax.devices()[0].device_kind
    if args.aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc("v5e:2x2", platform="tpu")
        sharding = SingleDeviceSharding(topo.devices[0])
        kind = topo.devices[0].device_kind + " (described, not attached)"
    ok = True
    for path in args.configs:
        with open(os.path.join(ROOT, path)) as f:
            config = json.load(f)
        for name, (lowered, *rest) in programs(config, sharding).items():
            t0 = time.perf_counter()
            compiled = lowered.compile()
            row = dict(check(compiled, *rest),
                       compile_s=round(time.perf_counter() - t0, 1))
            if args.dump:
                os.makedirs(args.dump, exist_ok=True)
                with open(os.path.join(args.dump, os.path.basename(path)
                                       + "." + name + ".hlo"), "w") as f:
                    f.write(comparable(compiled.as_text()))
            ok &= row["ok"]
            print(json.dumps({"config": os.path.basename(path),
                              "program": name, "device": kind, **row}),
                  flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
