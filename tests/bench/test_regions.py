"""Model regions: the op_name parser by hand, the region map of the tiny
dense and MoE train steps compiled on the CPU, and the attention roofline's
reader on a made-up trace and on one recorded on the chip."""
import gzip
import json

import pytest
import tiny

from perfbench import harness, regions
from perfbench import trace_reduce as tr

ROOFLINE = tiny.REPO / "perfbench" / "metrics" / "attention_roofline.train.py"
DECODER = harness.load_module(tiny.REPO / "perfbench/references/decoder.py")
SMOLLM = harness.load_cell(tiny.REPO, "smollm-train-hbm").config


@pytest.mark.parametrize("op_name,expected", [
    ("jit(train_step)/jvp(layers)/while/body/closed_call/checkpoint/attn/core"
     "/while/body/dot_general", ("attn/core", "fwd")),
    ("jit(train_step)/transpose(jvp(layers))/while/body/closed_call/"
     "checkpoint/rematted_computation/attn/core/while/body/dot_general",
     ("attn/core", "remat")),
    ("jit(train_step)/transpose(jvp(layers))/while/body/closed_call/mlp/"
     "jit(silu)/logistic", ("mlp", "bwd")),
    ("jit(train_step)/jvp(layers)/while/body/dynamic_update_slice",
     ("layers", "fwd")),
    ("jit(train_step)/transpose(jvp(embed))/scatter-add", ("embed", "bwd")),
    ("jit(train_step)/jvp(attn/qkv)/bsd,dhk->bshk", ("attn/qkv", "fwd")),
    ("jit(train_step)/optimizer/sqrt", ("optimizer", "fwd")),
    ("jit(train_step)/jvp()/div", ("other", "fwd")),
    ("jit(train_step)/jvp()/pad;jit(train_step)/transpose(jvp(head))/pad",
     ("head", "bwd")),
])
def test_region_of_by_hand(op_name, expected):
    assert regions.region_of(op_name) == expected


HLO = """HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %sine.1 = f32[4]{0} sine(%param_0), metadata={op_name="jit(train_step)/transpose(jvp(layers))/while/body/attn/core/sin"}
  ROOT %dynamic-update-slice.3 = f32[4]{0} copy(%sine.1)
}

ENTRY %main.9 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0), metadata={op_name="state"}
  %dot.2 = f32[4]{0} dot(%p, %p), metadata={op_name="jit(train_step)/jvp(layers)/while/body/mlp/dot_general" stack_frame_id=3}
  %bitcast_dynamic-update-slice_fusion.51 = f32[4]{0} fusion(%dot.2), kind=kLoop, calls=%fused_computation.1
  %copy-start.7 = (f32[4]{0}, f32[4]{0:S(5)}, u32[]{:S(2)}) copy-start(%bitcast_dynamic-update-slice_fusion.51)
  %copy-done.7 = f32[4]{0:S(5)} copy-done(%copy-start.7)
  ROOT %copy.4 = f32[4]{0} copy(%bitcast_dynamic-update-slice_fusion.51)
}
"""


def test_region_map_by_hand():
    rmap = regions.region_map(HLO)
    assert rmap["dot.2"] == ("mlp", "fwd")
    # no metadata of its own: the fused computation's named op decides
    assert rmap["bitcast_dynamic-update-slice_fusion.51"] == ("attn/core",
                                                              "bwd")
    assert rmap["copy.4"] == ("other", "fwd")
    # the host offload pass's unnamed copies to host memory
    assert rmap["copy-start.7"] == rmap["copy-done.7"] == ("offload", "fwd")
    assert sorted(regions.top_level_ops(HLO)) == [
        "bitcast_dynamic-update-slice_fusion.51", "dot.2"]
    assert tr.parse("%bitcast_dynamic-update-slice_fusion.51 = f32[4]{0} "
                    "fusion(f32[4]{0} %dot.2), kind=kLoop")[0] in rmap


@pytest.fixture(scope="module", params=["tiny-dense-hbm", "tiny-moe-dp"])
def compiled_step(request, tmp_path_factory):
    """A tiny cell's train step as the harness builds it (remat on, as in
    the benchmark), compiled on the CPU: its HLO text."""
    import jax

    root = tiny.make_root(tmp_path_factory.mktemp("regions"))
    cell = harness.load_cell(root, request.param)
    prog = harness.Program(cell, jax.devices()[:1],
                           harness.reference_module(cell))
    key = jax.random.PRNGKey(0)
    text = prog.jitted.lower(jax.eval_shape(prog.init, key),
                             jax.eval_shape(prog.feed, key, 0)
                             ).compile().as_text()
    prog.executor.close()
    return request.param, text


def test_every_named_op_of_the_step_is_in_a_region(compiled_step):
    name, text = compiled_step
    comps = regions._parse_hlo(text)
    rmap = regions.region_map(text)
    named = set()
    for instrs in comps.values():
        for instr, op, calls, _ in instrs:
            inner = comps.get(calls, [])
            if op or any(o for _, o, _, _ in inner):
                named.add(instr)
    tops = regions.top_level_ops(text)
    assert len(tops) > 100
    # `other` only where the compiler named nothing (the CPU's bf16 upcasts
    # and reduce-window wrappers): every op JAX named sits in a region
    strays = [t for t in tops if t in named and rmap[t][0] == "other"]
    assert strays == []
    unnamed = [t for t in tops if t not in named]
    assert len(unnamed) < len(tops) / 4
    found = {rmap[t] for t in tops}
    for direction in regions.DIRECTIONS:
        assert ("attn/core", direction) in found
    expected = {"embed", "layers", "norm", "attn/qkv", "attn/core",
                "attn/out", "head", "grad_norm", "optimizer"}
    expected |= ({"moe/router", "moe/dispatch", "moe/experts", "moe/combine"}
                 if "moe" in name else {"mlp"})
    assert expected <= {r for r, _ in found}
    assert {r for r, _ in found} <= set(regions.REGIONS) | {"other"}


def _summary(ops):
    return tr.Summary(0, 10, [tr.Device(ops=ops, flights=[])], [])


@pytest.mark.parametrize("core_ns,expected", [
    (2e9, 100.0 * 2.5e12 / (2.0 * 197e12)),
    (0.0, None),
])
def test_attention_roofline_reader(core_ns, expected):
    reader = harness.load_module(ROOFLINE)
    per_token = DECODER.attention_flops_per_token(SMOLLM, 2048)
    ops = [(0, 5e8, "dot.2", "matmul")]
    if core_ns:
        ops.append((1e9, 1e9 + core_ns,
                    "bitcast_dynamic-update-slice_fusion.51", "compute"))
    ctx = {"trace": _summary(ops), "seq_len": 2048, "step_text": HLO,
           "reference": DECODER, "config": SMOLLM,
           "tokens": 2.5e12 / per_token, "peak_flops": 197e12}
    value = reader.read(ctx)
    if expected is None:
        assert value is None
    else:
        assert value == pytest.approx(expected, rel=1e-12)


def test_attention_roofline_reader_needs_a_trace_and_a_workload():
    """The workload's step comes as its compiled text, from the harness."""
    reader = harness.load_module(ROOFLINE)
    ctx = {"trace": _summary([]), "seq_len": 2048, "tokens": 1,
           "peak_flops": 197e12, "step_text": None, "reference": DECODER,
           "config": SMOLLM}
    # a trace without the run's step is a fault, not a missing metric
    with pytest.raises(RuntimeError, match="compiled step's text"):
        reader.read(ctx)
    assert reader.read(dict(ctx, trace=None)) is None
    assert reader.read(dict(ctx, trace=None, step_text=HLO)) is None


def test_attention_roofline_counts_by_the_cells_reference():
    """Attention FLOPs come from the reference in ``ctx``, whatever model
    it is."""
    class Counts:
        @staticmethod
        def attention_flops_per_token(cfg, seq_len):
            return cfg["per_key"] * seq_len

    reader = harness.load_module(ROOFLINE)
    ops = [(1e9, 3e9, "bitcast_dynamic-update-slice_fusion.51", "compute")]
    ctx = {"trace": _summary(ops), "seq_len": 100, "step_text": HLO,
           "reference": Counts, "config": {"per_key": 5e6},
           "tokens": 1e6, "peak_flops": 1e15}
    assert reader.read(ctx) == pytest.approx(100 * 5e14 / (2.0 * 1e15))


# ---------------------------------------------------------------------------
# a trace recorded on one TPU v5e chip: two steps of ``smollm-train-hbm``
# (batch 8 x 2048, every state in HBM) in the harness's ``window`` span,
# with the Tracer on, and the region map of the compiled step it ran
# (``perfbench/region_report.py``)
# ---------------------------------------------------------------------------

DATA = tiny.REPO / "tests" / "bench" / "data"
HBM_TRACE = DATA / "smollm-train-hbm.v5e.xplane.pb.gz"
HBM_MAP = DATA / "smollm-train-hbm.v5e.regions.json.gz"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(HBM_MAP, "rt") as f:
        rmap = {k: tuple(v) for k, v in json.load(f).items()}
    return rmap, regions.reduce_file(HBM_TRACE, rmap, 1)


def test_recorded_regions_sum_to_the_step_module(recorded):
    rmap, t = recorded
    # host span `window`: 36,238,232 ns to 2,247,841,312 ns; the `XLA
    # Modules` line has two jit_train_step runs in it, 1.100281463 s and
    # 1.100434141 s: their ops' union falls short only by the gaps between
    assert (t.end_ns - t.start_ns) / 1e9 == pytest.approx(2.21160308)
    modules = 1.100281463 + 1.100434141
    busy = t.step_busy_s()
    assert modules - 1e-3 < busy <= modules
    top = t.regions(len(regions.REGIONS) + 1)
    # ops of the step never overlap: the regions add up to its busy time
    assert sum(s for _, s, _ in top) == pytest.approx(busy, rel=1e-9)
    for name, s, split in top:
        assert s == pytest.approx(sum(split.values()))
        assert s == pytest.approx(t.region_s(name))
    assert 1 - t.region_s("other") / busy > 0.99
    assert [name for name, _, _ in top[:3]] == ["attn/core", "mlp", "head"]
    # attn/core by direction, summed by a separate pass over the trace
    assert t.region_s("attn/core") == pytest.approx(1.935940308, abs=1e-9)
    assert t.region_s("attn/core", "remat") == pytest.approx(0.869175466,
                                                             abs=1e-9)
    assert t.region_s("attn/core", "bwd") > t.region_s("attn/core", "fwd")


def test_recorded_dynamic_update_slices_are_attention_residuals(recorded):
    rmap, _ = recorded
    for fusion in ("bitcast_dynamic-update-slice_fusion.51",
                   "bitcast_dynamic-update-slice_fusion.53"):
        assert rmap[fusion] == ("attn/core", "remat")


def test_recorded_idle_gaps_by_program_span(recorded):
    _, t = recorded
    labelled = t.idle_gaps_program()
    plain = tr.reduce_file(HBM_TRACE, 1).breakdown()["idle_gaps"]
    assert [s for _, s in labelled] == [s for _, s in plain]
    assert all(name != regions.OTHER for name, _ in labelled)
    # inside `dispatch_step`, the runtime allocates the step's outputs
    assert [name for name, _ in labelled[:2]] == [
        "AllocateOutputBuffersWithInputReuse"] * 2
    assert [name for name, _ in plain[:2]] == ["dispatch_step"] * 2


def test_recorded_step_spans(recorded):
    _, t = recorded
    steps = {}
    for plane in tr.load(HBM_TRACE).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "train_step":
                        steps[ev.start_ns] = dict(ev.stats)["step_num"]
    # one numbered host step span a window step (three checked steps and
    # two windows of ten came first)
    assert sorted(steps.values()) == [23, 24]
    # the device's own Steps line: one step a program launch (the feed's
    # jit_batch and jit_train_step), whatever the host annotates
    assert len(t.steps) == 4


def test_attention_roofline_reader_on_the_recorded_trace(monkeypatch,
                                                         recorded):
    rmap, t = recorded
    reader = harness.load_module(ROOFLINE)
    monkeypatch.setattr(regions, "region_map", lambda text: rmap)
    summary = tr.reduce_file(HBM_TRACE, 1)
    # no op of the feed's module shares a name with an attn/core op
    assert regions.summary_region_s(summary, rmap, "attn/core") == (
        t.region_s("attn/core"))
    ctx = {"trace": summary, "seq_len": 2048, "tokens": 2 * 8 * 2048,
           "peak_flops": 197e12, "step_text": "", "reference": DECODER,
           "config": SMOLLM}
    # 2 x 8 x 2048 tokens x 212.4 MFLOP over 1.935940308 s x 197e12
    assert reader.read(ctx) == pytest.approx(
        100 * 32768 * 3 * 30 * 2 * 2 * 9 * 64 * 1024.5
        / (1.935940308 * 197e12))
