"""``perfbench/program_trace.py`` and the eight readers built on it, on a
small trace WITH stats written by ``program_trace.write_planes`` from
``TRACE`` below (event names and stat keys as a v5e trace has them, PR 24:
a device op's scope path and program id are stats of its event METADATA).
Times in the source are nanoseconds."""

import os

import pytest

from deepspeed_tpu.telemetry import names
from perfbench import loader, program_trace

import pb_helpers as pb

US = 1000.0
MICRO, APPLY, RAGGED = 11, 18446744073709551000, 33     # program ids


def op(instr, start, end, program, scope=None):
    meta = {"program_id": program if program < 2**63 else program - 2**64,
            "hlo_category": "x"}
    if scope is not None:
        meta["tf_op"] = scope + ":"
    return (instr, start * US, end * US, {"device_offset_ps": 0}, meta)


def span(name, start, end, **counts):
    return (name, start * US, end * US, counts, {})


FLASH = ('%ds_flash_fwd.2 = (bf16[1,32,4096,128]{3,2,1,0}, f32[1,32,1,4096]'
         '{3,2,1,0}) custom-call(bf16[1,32,4096,128]{3,2,1,0} %x), '
         'custom_call_target="tpu_custom_call"')
FLASH_BWD = ('%ds_flash_bwd_dq.1 = bf16[1,32,4096,128]{3,2,1,0} custom-call('
             'bf16[1,32,4096,128]{3,2,1,0} %x), custom_call_target='
             '"tpu_custom_call"')
PAGED = ('%ds_paged_decode.16 = bf16[768,1,32,128]{3,2,1,0} custom-call('
         'bf16[768,1,32,128]{3,2,1,0} %q), custom_call_target='
         '"tpu_custom_call"')
HEAD = "%convolution_bitcast_fusion = f32[1,4096,32000]{2,1,0} fusion(bf16" \
       "[4096,4096]{1,0} %p), kind=kOutput, calls=%fused_computation.1"
GATHER = ("%all-gather-start.5 = (bf16[1024,4096]{1,0}, bf16[4096,4096]{1,0}"
          ") all-gather-start(bf16[1024,4096]{1,0} %param.7), dimensions={0}")

#: a 1000 us traced stretch of a training loop (two optimizer steps) ...
TRAIN = {
    "/device:TPU:0": {
        "XLA Modules": [
            (f"jit_ds_micro_flat({MICRO})", 0, 400 * US, {}, {}),
            (f"jit_ds_apply_update({APPLY})", 400 * US, 500 * US, {}, {}),
            (f"jit_ds_micro_flat({MICRO})", 500 * US, 900 * US, {}, {}),
            (f"jit_ds_apply_update({APPLY})", 900 * US, 1000 * US, {}, {})],
        "XLA Ops": [
            op("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 0,
               50, MICRO, "jit(ds_micro_flat)/jvp(LlamaModel)/ds.embed/"
               "embed_tokens/jit(_take)/gather"),
            op(FLASH, 50, 150, MICRO, "jit(ds_micro_flat)/jvp(LlamaModel)/"
               "layers_0/self_attn/ds_flash_fwd/pallas_call"),
            op(HEAD, 150, 250, MICRO, "jit(ds_micro_flat)/jvp(LlamaModel)/"
               "ds.lm_head_loss/lm_head/dot_general"),
            op("%fusion.16 = f32[4096,32000]{1,0} fusion(f32[8]{0} %p), "
               "kind=kOutput", 250, 300, MICRO, "jit(ds_micro_flat)/"
               "transpose(jvp(LlamaModel))/ds.lm_head_loss/lm_head/"
               "dot_general"),
            op(FLASH_BWD, 300, 340, MICRO, "jit(ds_micro_flat)/transpose("
               "jvp(LlamaModel))/layers_0/self_attn/ds_flash_bwd_dq/"
               "pallas_call"),
            op("%fusion.7 = bf16[4096,14336]{1,0} fusion(bf16[8]{0} %p), "
               "kind=kOutput", 340, 380, MICRO, "jit(ds_micro_flat)/"
               "transpose(jvp(LlamaModel))/jvp(LlamaModel)/checkpoint/"
               "rematted_computation/layers_0/mlp/gate_proj/dot_general"),
            op("%copy.3 = f32[8]{0} copy(f32[8]{0} %p)", 380, 400, MICRO),
            # [400, 420) idle, inside ds:train.apply
            op("%fusion.61 = bf16[32000,4096]{1,0} fusion(f32[8]{0} %p), "
               "kind=kLoop", 420, 500, APPLY,
               "jit(ds_apply_update)/convert_element_type"),
            op(GATHER, 500, 520, MICRO),
            op("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 520,
               700, MICRO, "jit(ds_micro_flat)/jvp(LlamaModel)/layers_1/"
               "post_attention_layernorm/mul"),
            # [700, 900) idle: the host is outside every ds: span
            op("%fusion.61 = bf16[32000,4096]{1,0} fusion(f32[8]{0} %p), "
               "kind=kLoop", 900, 1000, APPLY,
               "jit(ds_apply_update)/convert_element_type"),
        ],
    },
    "/device:TPU:1": {"XLA Ops": [op("%fusion.1 = f32[8]{0} fusion()", 0,
                                     1000, MICRO)]},
    "/host:CPU": {
        "python3": [
            span("pb:traced", 0, 1000),
            span("ds:train.micro", 0, 390, step=4, micro_step=4),
            span("ds:train.apply", 395, 480, step=4, micro_step=4),
            span("ds:train.report", 480, 490, step=4, micro_step=4),
            span("ds:train.shard_batch", 490, 500, step=5, micro_step=5),
            span("ds:train.micro", 500, 690, step=5, micro_step=5),
            span("ds:train.backward", 690, 699, step=5, micro_step=5),
            span("ds:train.accumulate", 692, 698, step=5, micro_step=5),
            span("pb:input", 750, 850),
            span("ds:train.apply", 900, 990, step=5, micro_step=5),
            span("$runtime", 0, 1000)],
    },
}

#: ... and of a serving loop: two ragged steps and a burst
SERVE = {
    "/device:TPU:0": {
        "XLA Modules": [
            (f"jit_ds_ragged_step_llama({RAGGED})", 0, 1000 * US, {}, {})],
        "XLA Ops": [
            op(PAGED, 0, 600, RAGGED, "jit(ds_ragged_step_llama)/ds.attn/"
               "ds_paged_decode/pallas_call"),
            op("%fusion.4 = bf16[768,14336]{1,0} fusion(bf16[8]{0} %p), "
               "kind=kOutput", 600, 800, RAGGED,
               "jit(ds_ragged_step_llama)/ds.mlp/dot_general"),
            op("%slice_bitcast_fusion.1 = bf16[2,560,128,8,128]{4,3,2,1,0} "
               "fusion(bf16[8]{0} %p), kind=kLoop", 800, 900, RAGGED,
               "jit(ds_ragged_step_llama)/ds.kv_cache/squeeze"),
            # [900, 1000) idle
        ],
    },
    "/host:CPU": {
        "python3": [
            span("pb:traced", 0, 1000),
            span("ds:serve.step", 0, 400, step=1, kind="ragged", running=3,
                 queued=0, token_budget=768, live_tokens=500,
                 prefill_tokens=440, decode_tokens=60, grid_pages=20736,
                 live_pages=2000, short_pages=1500, burst_k=0, preempts=0),
            span("ds:serve.admit", 0, 10),
            span("ds:serve.admitted", 5, 5, uid=7),
            span("ds:serve.build_batch", 10, 30),
            span("ds:serve.launch", 30, 50),
            span("ds:serve.fetch", 50, 380),
            span("ds:serve.dispatch", 380, 400),
            span("ds:serve.step", 400, 700, step=2, kind="ragged", running=3,
                 queued=0, token_budget=768, live_tokens=268,
                 prefill_tokens=208, decode_tokens=60, grid_pages=20736,
                 live_pages=1000, short_pages=900, burst_k=0, preempts=0),
            span("ds:serve.fetch", 420, 690),
            span("ds:serve.step", 700, 1000, step=3, kind="burst", running=3,
                 queued=0, token_budget=1040, live_tokens=48,
                 prefill_tokens=0, decode_tokens=48, grid_pages=28080,
                 live_pages=300, burst_k=16, preempts=0),
            span("ds:serve.fetch", 710, 950),
            span("ds:serve.dispatch", 950, 1000)],
    },
}


def _write(tmp_path, planes, cell="cell"):
    d = tmp_path / ".perfbench_trace" / cell / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    path = str(d / "host.xplane.pb")
    program_trace.write_planes(planes, path)
    return path


@pytest.fixture(scope="module")
def train(tmp_path_factory):
    path = _write(tmp_path_factory.mktemp("train"), TRAIN)
    return program_trace.reduce_file(path, names)


@pytest.fixture(scope="module")
def serve(tmp_path_factory):
    path = _write(tmp_path_factory.mktemp("serve"), SERVE)
    return program_trace.reduce_file(path, names)


def test_the_wire_reader_gives_back_what_was_written(tmp_path):
    planes = program_trace.read_file(_write(tmp_path, TRAIN))
    assert set(planes) == {"/device:TPU:0", "/host:CPU"}     # chip 0 only
    name, start, end, stats, meta = planes["/device:TPU:0"]["XLA Ops"][1]
    assert (name, start, end) == (FLASH, 50 * US, 150 * US)
    assert meta["tf_op"].endswith("self_attn/ds_flash_fwd/pallas_call:")
    assert meta["program_id"] == MICRO
    apply_op = planes["/device:TPU:0"]["XLA Ops"][7]
    assert apply_op[4]["program_id"] == APPLY - 2**64        # int64 on disk
    host = planes["/host:CPU"]["python3"]
    assert [e[0] for e in host if e[0].startswith("$")] == []  # left out
    micro = [e for e in host if e[0] == "ds:train.micro"][1]
    assert micro[3] == {"step": 5, "micro_step": 5}
    # the same file through jax's own reader: names and times agree
    from jax.profiler import ProfileData
    from perfbench import xplane
    theirs = xplane.read_planes(ProfileData.from_file(
        program_trace.find_trace(str(tmp_path))))
    assert theirs["/device:TPU:0"]["XLA Ops"] == \
        [e[:3] for e in planes["/device:TPU:0"]["XLA Ops"]]


def test_device_time_by_layer_class_and_the_unclassed_rest(train):
    by = train["device_ms_by_class"]
    assert by == pytest.approx({
        "embed": 0.05, "flash_kernel": 0.14, "lm_head": 0.15, "mlp": 0.04,
        "optimizer": 0.18, "collective": 0.02, "norm": 0.18,
        "unclassed": 0.02})
    assert train["busy_ms"] == pytest.approx(0.78)
    assert sum(by.values()) == pytest.approx(train["busy_ms"])
    assert train["unclassed_share"] == pytest.approx(100 * 0.02 / 0.78)
    assert train["unclassed_top"] == [["copy.3 copy f32[8]",
                                       pytest.approx(0.02)]]
    assert train["device_ms_by_kernel"] == pytest.approx(
        {"ds_flash_fwd": 0.1, "ds_flash_bwd_dq": 0.04})
    assert train["lm_head_ms"] == pytest.approx(
        {"forward": 0.1, "backward": 0.05})
    assert train["recompute_ms"] == pytest.approx(0.04)
    assert train["optimizer_program_ms"] == pytest.approx(0.18)
    assert train["modules_in_window"] == {"jit_ds_micro_flat": 2,
                                          "jit_ds_apply_update": 2}


def test_host_self_time_and_idle_gap_labels(train):
    spans = train["host_spans"]
    assert spans["train.micro"] == {"n": 2, "total_ms": pytest.approx(0.58),
                                    "self_ms": pytest.approx(0.58)}
    # self time = duration minus what the children cover
    assert spans["train.backward"]["total_ms"] == pytest.approx(0.009)
    assert spans["train.backward"]["self_ms"] == pytest.approx(0.003)
    assert train["train_steps"] == 2
    # [400, 420) lies in ds:train.apply; [700, 900) in no ds: span, and the
    # benchmark's own span says what the host did there
    assert train["idle_ms_by_span"] == pytest.approx(
        {"outside ds: spans (pb:input)": 0.2, "train.apply": 0.02})
    assert train["idle_ms"] == pytest.approx(0.22)
    assert train["idle_outside_share"] == pytest.approx(100 * 0.2 / 0.22)
    assert train["long_gaps_without_label"] == 1


def test_serving_steps_their_counts_and_host_time(serve):
    s = serve["serve"]
    assert s["steps"] == 3 and s["kinds"] == {"ragged": 2, "burst": 1}
    assert s["ragged_sums"] == {
        "token_budget": 1536, "live_tokens": 768, "prefill_tokens": 648,
        "decode_tokens": 120, "grid_pages": 41472, "live_pages": 3000,
        "short_pages": 2400}
    # step minus its fetch: 70 + 30 + 60 us
    assert s["host_ms"] == pytest.approx(0.16)
    assert serve["host_spans"]["serve.step"]["self_ms"] == \
        pytest.approx(1.0 - 0.33 - 0.27 - 0.24 - 0.01 - 0.02 - 0.02 - 0.02
                      - 0.05)
    assert serve["device_ms_by_class"] == pytest.approx(
        {"paged_kernel": 0.6, "mlp": 0.2, "kv_cache": 0.1})
    assert serve["unclassed_share"] == 0.0
    assert serve["idle_ms_by_span"] == pytest.approx({"serve.dispatch": 0.1})
    assert serve["long_gaps_without_label"] == 0


@pytest.mark.parametrize("instr,scope,program,cls", [
    (FLASH, "jit(m)/jvp(M)/layers_0/self_attn/ds_flash_fwd/pallas_call",
     "jit_ds_micro_flat", "flash_kernel"),
    (PAGED, "", "jit_ds_ragged_step_llama", "paged_kernel"),
    ("%ds_fused_adam.3 = f32[8]{0} custom-call(f32[8]{0} %p), "
     "custom_call_target=\"tpu_custom_call\"", "", "jit_ds_apply_update",
     "other_kernel"),
    (GATHER, "", "jit_ds_micro_flat", "collective"),
    # a compute fusion that READS a gathered operand, in the optimizer step
    ("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %all-gather-done.2), kind=kLoop",
     "jit(ds_apply_update)/mul", "jit_ds_apply_update", "optimizer"),
    (HEAD, "jit(m)/transpose(jvp(M))/ds.lm_head_loss/lm_head/dot_general",
     "jit_ds_micro_flat", "lm_head"),
    (HEAD, "jit(s)/ds.lm_head/dot_general", "jit_ds_ragged_step_llama",
     "lm_head"),
    ("%fusion.1 = f32[8]{0} fusion()", "jit(m)/jvp(M)/layers_3/mlp/up_proj/"
     "dot_general", "jit_ds_micro_flat", "mlp"),
    ("%fusion.1 = f32[8]{0} fusion()", "jit(s)/ds.attn/dot_general",
     "jit_ds_ragged_step_llama", "attention"),
    # the cache scatter lies INSIDE ds.attn since PR 28: its own class
    ("%fusion.1 = f32[8]{0} fusion()", "jit(s)/ds.attn/ds.kv_cache/scatter",
     "jit_ds_ragged_step_llama", "kv_cache"),
    ("%fusion.1 = f32[8]{0} fusion()", "jit(m)/jvp(M)/norm/mul",
     "jit_ds_micro_flat", "norm"),
    ("%fusion.1 = f32[8]{0} fusion()", "jit(m)/jvp(M)/layers_0/add",
     "jit_ds_micro_flat", "residual"),
    ("%fusion.1 = f32[8]{0} fusion()", "jit(m)/convert_element_type",
     "jit_ds_micro_flat", "unclassed"),
    ("%copy.1 = f32[8]{0} copy(f32[8]{0} %p)", "", "", "unclassed"),
], ids=lambda v: v[:28] if isinstance(v, str) else str(v))
def test_an_op_gets_one_class_by_the_programs_names(instr, scope, program,
                                                    cls):
    meta = {"tf_op": scope + ":"} if scope else {}
    assert program_trace.classify(instr, meta, program, names) == cls


READERS = {
    # metric -> (trace, expected value)
    "serve_live_token_share": (SERVE, 100 * 768 / 1536),
    "serve_host_ms_per_step": (SERVE, 0.16 / 3),
    "serve_paged_kernel_ms_per_step": (SERVE, 0.6 / 3),
    "train_optimizer_ms_per_step": (TRAIN, 0.18 / 2),
    "train_lm_head_ms_per_step": (TRAIN, 0.15 / 2),
    "train_flash_kernel_ms_per_step": (TRAIN, 0.14 / 2),
    "train_recompute_ms_per_step": (TRAIN, 0.04 / 2),
}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader(metric, tmp_path, monkeypatch, capsys):
    planes, expected = READERS[metric]
    reader = loader.load_reader(pb.ROOT, metric)
    monkeypatch.setattr(program_trace, "ROOT", str(tmp_path))
    monkeypatch.setattr(program_trace, "_CACHE", {})
    record = {"trace": {"busy_s": 1.0}, "traced_steps": 2}
    # nothing to read: no trace file; an untraced run; the other job's trace
    assert reader.read(record) is None
    _write(tmp_path, planes)
    assert reader.read({"trace": None}) is None
    assert reader.read(record) == pytest.approx(expected)
    assert reader.read(record) == pytest.approx(expected)   # reduced once
    assert capsys.readouterr().out.count("INFO program_spans: ") == 1
    other = SERVE if planes is TRAIN else TRAIN
    monkeypatch.setattr(program_trace, "_CACHE", {})
    newer = _write(tmp_path, other, cell="newer")
    os.utime(newer, (2e9, 2e9))
    assert reader.read(record) is None


def test_a_program_without_the_names_gives_nothing(tmp_path, monkeypatch):
    """The parent commit has no ``deepspeed_tpu.telemetry.names``: the
    readers return None there and do not raise."""
    import sys
    monkeypatch.setattr(program_trace, "ROOT", str(tmp_path))
    monkeypatch.setattr(program_trace, "_CACHE", {})
    _write(tmp_path, TRAIN)
    import deepspeed_tpu.telemetry
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.telemetry.names", None)
    monkeypatch.delattr(deepspeed_tpu.telemetry, "names")
    assert program_trace.program_names() is None
    record = {"trace": {"busy_s": 1.0}}
    for metric in READERS:
        assert loader.load_reader(pb.ROOT, metric).read(record) is None
