"""The trace reduction on a small synthetic trace kept beside this file
(``data/synthetic.xplane.pb``, written by ``xplane.write_planes`` from
``SYNTHETIC`` below; ``test_data_file_is_what_the_source_says`` keeps the two
together).  Times in the source are nanoseconds."""

import os

import pytest

from perfbench import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "synthetic.xplane.pb")
US = 1000.0

#: event names as a v5e trace has them (PR 23, my chip runs): the whole HLO
#: instruction, operands and attributes included
FUSION_READING_A_GATHER = (
    "%fusion.77 = bf16[4096,14336]{1,0:T(8,128)(2,1)} fusion(bf16[4096,4096]"
    "{0,1:T(8,128)(2,1)} %all-gather-done.12, bf16[4096,14336]{1,0:T(8,128)"
    "(2,1)S(1)} %copy-done.18), kind=kOutput, calls=%fused_computation.77")
FLASH_KERNEL = (
    "%self_attn.6 = (bf16[1,32,4096,128]{3,2,1,0:T(8,128)(2,1)}, "
    "f32[1,32,1,4096]{3,2,1,0:T(1,128)}) custom-call(bf16[1,32,4096,128]"
    "{3,2,1,0:T(8,128)(2,1)} %maximum_bitcast_fusion, f32[32,1]{1,0:T(8,128)}"
    " %broadcast.9), custom_call_target=\"tpu_custom_call\", "
    "frontend_attributes={kernel_metadata={}}")
REDUCE_SCATTER_FUSION = (
    "%all-reduce-scatter.3 = f32[1024,4096]{1,0:T(8,128)} fusion(f32[4096,"
    "4096]{1,0:T(8,128)} %fusion.41), kind=kCustom, "
    "calls=%all-reduce-scatter.3.clone")
ASYNC_GATHER = (
    "%all-gather-start.5 = (bf16[1024,4096]{1,0:T(8,128)(2,1)}, bf16[4096,"
    "4096]{1,0:T(8,128)(2,1)}) all-gather-start(bf16[1024,4096]{1,0:T(8,128)"
    "(2,1)} %param.7), replica_groups={{0,1,2,3}}, dimensions={0}")

#: two chips, a 1000 us traced window; chip 0: ops over [0,100) [100,300)
#: [400,500) [500,700) [900,1000) = 700 us busy, idle over [300,400) and
#: [700,900); an async all-gather on a line of its own over [250,450)
SYNTHETIC = {
    "/device:TPU:0": {
        "XLA Ops": [(FUSION_READING_A_GATHER, 0, 100 * US),
                    (FLASH_KERNEL, 100 * US, 300 * US),
                    ("fusion.2", 400 * US, 500 * US),
                    (REDUCE_SCATTER_FUSION, 500 * US, 700 * US),
                    ("fusion.11", 900 * US, 1000 * US)],
        "Async XLA Ops": [(ASYNC_GATHER, 250 * US, 450 * US)],
        "XLA Modules": [("jit_micro(1)", 0, 1000 * US)],
        "Steps": [("0", 0, 1000 * US)],
    },
    "/device:TPU:1": {
        "XLA Ops": [("fusion.1", 0, 500 * US)],
    },
    "/host:CPU": {
        "python3": [("pb:traced", 0, 1000 * US),
                    ("pb:forward", 0, 320 * US),
                    ("pb:step", 320 * US, 760 * US),
                    ("pb:wait_for_device", 760 * US, 1000 * US),
                    ("$other", 0, 1000 * US)],
    },
}


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce_file(DATA)


def test_data_file_is_what_the_source_says(tmp_path):
    from jax.profiler import ProfileData
    fresh = str(tmp_path / "fresh.xplane.pb")
    xplane.write_planes(SYNTHETIC, fresh)
    read = lambda p: xplane.read_planes(ProfileData.from_file(p))
    assert read(fresh) == read(DATA)
    assert read(DATA)["/device:TPU:0"]["XLA Ops"][1] == \
        (FLASH_KERNEL, 100 * US, 300 * US)


def test_busy_idle_and_window(reduced):
    assert reduced["devices"] == 2
    assert reduced["window_s"] == pytest.approx(1000e-6)
    assert reduced["busy_s_by_device"] == pytest.approx([700e-6, 500e-6])
    assert reduced["busy_s"] == pytest.approx(600e-6)
    assert reduced["op_events"] == 5
    assert reduced["longest_gap_s"] == pytest.approx(200e-6)


def test_collectives_and_their_exposed_part(reduced):
    # all-gather [250,450) on its own line + all-reduce-scatter [500,700);
    # the fusion over [0,100) that READS a gathered operand is compute
    assert reduced["collective_s"] == pytest.approx(400e-6)
    # no other op runs over [300,400) and [500,700)
    assert reduced["collective_exposed_s"] == pytest.approx(300e-6)


def test_mosaic_share_and_top_ops(reduced):
    assert reduced["mosaic_s"] == pytest.approx(200e-6)
    top = dict((n, s) for n, s in reduced["top_ops"])
    assert top["fusion.77 fusion bf16[4096,14336]"] == pytest.approx(100e-6)
    assert top["self_attn.6 custom-call bf16[1,32,4096,128]"] == \
        pytest.approx(200e-6)
    assert top["all-reduce-scatter.3 fusion f32[1024,4096]"] == \
        pytest.approx(200e-6)
    assert reduced["top_ops"][0][1] == pytest.approx(200e-6)


@pytest.mark.parametrize("name,collective,mosaic", [
    (FUSION_READING_A_GATHER, False, False),      # operand text is not the op
    (REDUCE_SCATTER_FUSION, True, False),         # named by the TPU compiler
    (ASYNC_GATHER, True, False),
    ("%all-gather-done.12 = bf16[4096,4096]{1,0} all-gather-done((bf16[1024,"
     "4096]{1,0}, bf16[4096,4096]{1,0}) %all-gather-start.12)", True, False),
    ("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %collective-permute-done.2), "
     "kind=kLoop, calls=%fused_computation.9", False, False),
    (FLASH_KERNEL, False, True),
    ("%custom-call.4 = f32[8]{0} custom-call(f32[8]{0} %p), "
     "custom_call_target=\"AllocateBuffer\"", False, False),
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %self_attn.6), kind=kLoop, "
     "calls=%fused_computation.3", False, False),
    ("all-gather-start.5", True, False),          # bare names, as older
    ("custom-call.7", False, True),               # traces gave them
    ("fusion.1", False, False),
], ids=lambda v: v[:24] if isinstance(v, str) else str(v))
def test_ops_are_classed_by_their_own_name_and_opcode(name, collective,
                                                      mosaic):
    assert xplane.is_collective(name) is collective
    assert xplane.is_mosaic(name) is mosaic


def test_op_label_reads_an_hlo_instruction():
    text = ("%self_attn.6 = (bf16[1,32,4096,128]{3,2,1,0:T(8,128)(2,1)}, "
            "f32[1,32,1,4096]{3,2,1,0}) custom-call(bf16[1,32,4096,128]{3,2,1,"
            "0} %x), custom_call_target=\"tpu_custom_call\"")
    assert xplane.op_label(text) == \
        "self_attn.6 custom-call bf16[1,32,4096,128]"
    assert xplane.is_mosaic(text)
    text = ("%convolution_bitcast_fusion = f32[1,4096,32000]{2,1,0:T(8,128)} "
            "fusion(bf16[4096,32000]{1,0} %p), kind=kOutput")
    assert xplane.op_label(text) == \
        "convolution_bitcast_fusion fusion f32[1,4096,32000]"
    assert not xplane.is_mosaic(text)
    assert xplane.op_parts(
        "%all-gather-start.3 = (bf16[8]{0}) all-gather-start(bf16[2]{0} %p)"
    ) == ("all-gather-start.3", "all-gather-start")
    assert xplane.op_label("fusion.1") == "fusion.1"


def test_idle_gaps_are_labelled_by_the_benchmark_span(reduced):
    gaps = dict((n, s) for n, s in reduced["idle_gaps"])
    # the middle of [300,400) lies in pb:step, that of [700,900) in
    # pb:wait_for_device; the window span itself and other host events
    # label nothing
    assert gaps == {"step": pytest.approx(100e-6),
                    "wait_for_device": pytest.approx(200e-6)}
    assert reduced["idle_gaps"][0][0] == "wait_for_device"


def test_n_devices_limits_the_planes_and_no_device_plane_gives_nothing():
    from jax.profiler import ProfileData
    planes = xplane.read_planes(ProfileData.from_file(DATA))
    assert xplane.reduce_planes(planes, n_devices=1)["busy_s"] == \
        pytest.approx(700e-6)
    assert xplane.reduce_planes({"/host:CPU": planes["/host:CPU"]}) is None


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert xplane.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert xplane.clip([(0, 5), (8, 12)], 2, 10) == [(2, 5), (8, 10)]
