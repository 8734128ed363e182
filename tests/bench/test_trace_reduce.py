"""The trace reduction: HLO classification and interval arithmetic by hand,
and a trace recorded on one TPU v5e chip against numbers checked by hand in
its dump: two steps of ``smollm-train-hostopt`` (batch 16 x 2048, optimizer
state in pinned host memory) inside the harness's ``window`` span."""
from pathlib import Path

import pytest

from perfbench import trace_reduce as tr

SMOLLM = Path(__file__).parent / "data" / "smollm-train-hostopt.v5e.xplane.pb.gz"


@pytest.mark.parametrize("text,cls", [
    ("%convolution_add_fusion.3 = bf16[16,2048,576]{1,2,0:T(8,128)(2,1)S(1)} "
     "fusion(bf16[16,2048,576]{1,2,0} %a), kind=kOutput, calls=%c", "matmul"),
    ("%convolution.7 = f32[8,8]{1,0} convolution(f32[8,4]{1,0} %a, f32[4,8]"
     "{1,0} %b), dim_labels=bf_io->bf", "matmul"),
    ("%fusion.27 = u32[16]{0:T(128)S(1)} fusion(), kind=kLoop, calls=%f",
     "compute"),
    ("%add_fusion.1 = f32[8]{0} fusion(f32[8]{0} %all-gather.5), kind=kLoop",
     "compute"),
    ("%all-gather-start.2 = (bf16[4]{0}, bf16[16]{0}) all-gather-start("
     "bf16[4]{0} %p), dimensions={0}", "collective"),
    ("%all-reduce.1 = f32[1024]{0} all-reduce(f32[1024]{0} %x), "
     "to_apply=%add", "collective"),
    ("%copy-done.65 = f32[30,1536,576]{1,2,0:T(8,128)S(5)} copy-done(("
     "f32[30,1536,576]{1,2,0:T(8,128)S(5)}, f32[30,1536,576]{1,2,0}, "
     "u32[]{:S(2)}) %copy-start.65)", "host_copy"),
    ("%copy-done.1 = u32[2]{0:T(128)S(1)} copy-done((u32[2]{0:T(128)S(1)}, "
     "u32[2]{0:T(128)}, u32[]{:S(2)}) %copy-start.1)", "wait"),
    ("%while.196 = (s32[]{:T(128)}, bf16[16,2048,576]{1,2,0}) while((s32[],"
     " bf16[16,2048,576]) %t), condition=%c, body=%b", "control"),
])
def test_classify_by_hand(text, cls):
    assert tr.classify(text) == cls


def test_intervals_by_hand():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (25, 26)]) == [
        (0, 2), (4, 8), (22, 25), (26, 30)]
    dev = tr.Device(ops=[(0, 4, "dot", "matmul"), (6, 8, "add", "compute"),
                         (8, 9, "copy-done", "host_copy")],
                    flights=[(3, 9, "host_copy")])
    # in flight 3..9; compute covers 3..4 and 6..8: exposed 4..6 and 8..9
    assert dev.exposed_ns("host_copy") == 3.0
    summary = tr.Summary(0, 10, [dev], [(4, 7, "dispatch_step")])
    assert summary.busy_s == pytest.approx(7e-9)
    assert summary.gaps() == [(4, 6), (9, 10)]
    assert summary.breakdown()["idle_gaps"][0] == ["dispatch_step", 2e-9]


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce_file(SMOLLM, 1)


def test_recorded_window_and_busy_time(recorded):
    # host span `window`: 46,583,927 ns to 9,105,200,224 ns
    assert recorded.window_s == pytest.approx(9.058616297, abs=1e-9)
    # the `XLA Modules` line has two jit_train_step runs inside the window,
    # 2.74663736 s and 2.747022972 s, and one jit_batch of 11.257 us; the
    # union of their ops falls short of that only by the gaps between ops
    modules = 2.74663736 + 2.747022972 + 11.257e-6
    assert modules - 1e-3 < recorded.busy_s <= modules


def test_recorded_op_classes(recorded):
    # 5,406 convolution instructions and kind=kOutput fusions in the window,
    # summed by a separate pass over the dump
    assert recorded.class_s("matmul") == pytest.approx(2.829552628, abs=1e-9)
    assert not recorded.present("collective")
    assert recorded.present("host_copy")
    idle = recorded.window_s - recorded.busy_s
    assert 0 < recorded.exposed_share("host_copy") * recorded.window_s < idle


def test_recorded_idle_gaps_by_host_span(recorded):
    # the first jit_train_step starts on the device at 2,178,893,967 ns,
    # 2.1323 s after the window opens: the host is still in dispatch_step
    gaps = recorded.breakdown()["idle_gaps"]
    assert len(gaps) <= 10
    assert [name for name, _ in gaps[:2]] == ["dispatch_step"] * 2
    assert gaps[0][1] == pytest.approx((2178893967 - 46583927) / 1e9,
                                       abs=1e-5)
    ops = recorded.breakdown()["device_ops"]
    assert len(ops) == 10 and ops[0][1] >= ops[-1][1] > 0
