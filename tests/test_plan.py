"""Declarative memory planner (repro/plan.py): the three acceptance
scenarios (roomy-HBM / HBM-starved / HBM+DRAM-starved) derive device / host
/ nvme-dominant placements; predicted peak residency upper-bounds what a
real executor step measures; the plan round-trips through JSON and
``to_run_config``; config validation raises catchable ``ValueError``s; and
``schedule.default_prefetch_layers`` holds at its edge cases."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.config import (OffloadConfig, ParallelConfig, RunConfig, SHAPES,
                          ShapeConfig, TrainConfig, make_offload)
from repro.core.executor import InfinityExecutor
from repro.core.schedule import LayerSchedule, default_prefetch_layers
from repro.launch.mesh import make_local_mesh
from repro.plan import (HardwareSpec, InfinityPlan, OVERRIDABLE, plan_run,
                        state_bytes)

FULL = configs.get("smollm-135m")
TRAIN_4K = SHAPES["train_4k"]


# ---------------------------------------------------------------------------
# acceptance: the three hardware scenarios on smollm-135m / train_4k
# ---------------------------------------------------------------------------


def test_roomy_hbm_derives_device_placement():
    hw = HardwareSpec(n_devices=16, device_mem=32e9, host_mem=1.5e12,
                      nvme_capacity=28e12)
    p = plan_run(FULL, TRAIN_4K, hw)
    assert p.tiers == {"param": "device", "grad": "device", "opt": "device",
                      "act": "device"}
    assert p.feasible and p.engine == "pjit"
    assert p.predictions["efficiency"] == 1.0
    # full residency predicted when nothing streams
    sb = state_bytes(FULL, TRAIN_4K, 16)
    assert p.predictions["peak_resident_param_bytes"] == sb.param


def test_hbm_starved_derives_host_placement():
    hw = HardwareSpec(n_devices=16, device_mem=1e9, host_mem=1.5e12,
                      nvme_capacity=28e12)
    p = plan_run(FULL, TRAIN_4K, hw)
    assert p.feasible
    assert p.param_tier == "host" and p.opt_tier == "host"
    assert p.grad_tier == "host"
    assert p.predictions["efficiency"] < 1.0
    # every demotion carries its Eq.-level arithmetic
    assert "usable HBM" in p.why("opt_tier")


def test_hbm_and_dram_starved_derives_nvme_placement():
    hw = HardwareSpec(n_devices=16, device_mem=1e9, host_mem=8e9,
                      nvme_capacity=28e12)
    p = plan_run(FULL, TRAIN_4K, hw)
    assert p.feasible
    assert p.param_tier == "nvme" and p.opt_tier == "nvme"
    assert p.grad_tier == "nvme"
    # NVMe-resident params select the layered zero3 engine and a window
    # strictly below the layer count
    assert p.engine == "zero3"
    assert 1 <= p.prefetch_layers < FULL.n_layers
    # activations cannot reach NVMe: they land on host with grad accum
    # shrinking the microbatch until Eq. 3 fits
    assert p.act_tier == "host"
    assert p.grad_accum > 1


def test_prefill_plan_charges_params_only():
    """Serving shapes hold no grads/optimizer: a prefill plan on hardware
    that fits the bf16 params must stay all-device instead of demoting
    tiers for training-only state."""
    shape = ShapeConfig("prefill-t", 1024, 8, "prefill")
    sb = state_bytes(FULL, shape, 1)
    assert sb.grad == 0 and sb.opt == 0 and sb.act_ckpt == 0
    hw = HardwareSpec(n_devices=1, device_mem=1.2e9, host_mem=2e9)
    p = plan_run(FULL, shape, hw)
    assert p.feasible
    assert p.tiers == {"param": "device", "grad": "device", "opt": "device",
                      "act": "device"}


def test_grad_accum_divides_global_batch():
    """Derived grad_accum must divide the global batch (the engine reshapes
    to (accum, batch // accum, ...)), even for non-power-of-two batches —
    and lowering it onto the zero3 engine warns that accumulation is a
    pjit-engine knob."""
    shape = ShapeConfig("odd-batch", 4096, 6, "train")
    hw = HardwareSpec(n_devices=1, device_mem=50e6, host_mem=500e6,
                      nvme_capacity=1e12)
    p = plan_run(FULL, shape, hw)
    assert p.feasible
    assert p.grad_accum > 1
    assert shape.global_batch % p.grad_accum == 0
    assert p.engine == "zero3"
    assert any("pjit-engine knob" in w for w in p.warnings)


def test_host_params_that_cannot_transit_hbm_are_not_feasible():
    """The structural limit: host-homed params still assemble fully on
    device inside the step. When 2N alone exceeds usable HBM, a big host
    DRAM must NOT buy a 'feasible' host plan — without NVMe the plan is
    infeasible with an explanatory warning; with NVMe the planner escalates
    to the layered row stream, the only O(window)-residency placement."""
    # usable HBM = 210 MB < 2N = 269 MB for smollm-135m
    no_nvme = HardwareSpec(n_devices=1, device_mem=300e6, host_mem=2e12,
                           nvme_capacity=0.0)
    p = plan_run(FULL, TRAIN_4K, no_nvme)
    assert p.param_tier == "host"
    assert not p.feasible
    assert any("structural limit" in w for w in p.warnings)
    with_nvme = dataclasses.replace(no_nvme, nvme_capacity=28e12)
    p2 = plan_run(FULL, TRAIN_4K, with_nvme)
    assert p2.param_tier == "nvme" and p2.engine == "zero3"
    assert p2.feasible
    assert "escalated" in p2.why("param_tier")


def test_no_nvme_and_no_room_is_infeasible_not_an_exception():
    hw = HardwareSpec(n_devices=1, device_mem=1e6, host_mem=1e6,
                      nvme_capacity=0.0)
    p = plan_run(FULL, TRAIN_4K, hw)
    assert not p.feasible
    assert any("INFEASIBLE" in w for w in p.warnings)


def test_min_device_mem_objective_offloads_everything():
    hw = HardwareSpec(n_devices=16, device_mem=32e9, host_mem=1.5e12,
                      nvme_capacity=28e12)
    p = plan_run(FULL, TRAIN_4K, hw, objective="min_device_mem")
    assert p.param_tier == "nvme" and p.opt_tier == "nvme"
    assert p.act_tier == "host"


# ---------------------------------------------------------------------------
# overrides: legacy knobs as per-field forces, with a loud diff
# ---------------------------------------------------------------------------


def test_override_contradicting_feasibility_is_loud():
    # one 1-GB device: usable HBM (0.7 GB) cannot hold the 1.6 GB optimizer
    hw = HardwareSpec(n_devices=1, device_mem=1e9, host_mem=8e9,
                      nvme_capacity=28e12)
    p = plan_run(FULL, TRAIN_4K, hw, overrides={"opt_tier": "device"})
    assert p.opt_tier == "device"  # honored...
    assert not p.feasible  # ...but the arithmetic says no
    assert any("override opt_tier='device'" in w for w in p.warnings)
    assert any("INFEASIBLE" in w and "device" in w for w in p.warnings)


def test_override_pjit_with_nvme_params_warns_residency_scope():
    hw = HardwareSpec(n_devices=16, device_mem=1e9, host_mem=8e9,
                      nvme_capacity=28e12)
    p = plan_run(FULL, TRAIN_4K, hw, overrides={"engine": "pjit"})
    assert p.engine == "pjit"
    assert any("host *staging*" in w for w in p.warnings)


def test_override_unknown_field_raises():
    with pytest.raises(ValueError, match="unknown plan override"):
        plan_run(FULL, TRAIN_4K, HardwareSpec(), overrides={"nope": 1})


# ---------------------------------------------------------------------------
# quantized tier transport in the plan arithmetic
# ---------------------------------------------------------------------------

_NVME_HW = HardwareSpec(n_devices=16, device_mem=1e9, host_mem=8e9,
                        nvme_capacity=28e12)


def test_param_quant_override_deepens_window_and_shrinks_wire():
    from repro.core import qformat

    base = plan_run(FULL, TRAIN_4K, _NVME_HW)
    assert base.param_tier == "nvme" and base.param_quant == "none"
    p = plan_run(FULL, TRAIN_4K, _NVME_HW, overrides={"param_quant": "q8"})
    ratio = qformat.compression_ratio("q8")
    assert p.param_quant == "q8"
    # pinned staging holds ratio-x more wire rows -> the window deepens
    assert p.prefetch_layers > base.prefetch_layers
    # predicted wire traffic = logical / ratio; logical is unchanged
    assert p.predictions["param_step_read_bytes"] == \
        base.predictions["param_step_read_bytes"]
    assert p.predictions["param_step_read_wire_bytes"] == pytest.approx(
        p.predictions["param_step_read_bytes"] / ratio)
    assert p.predictions["param_step_write_wire_bytes"] == pytest.approx(
        p.predictions["param_step_write_bytes"] / ratio)
    assert p.predictions["param_compression_ratio"] == pytest.approx(ratio)
    # the decision trail names the format and the deepened window
    assert p.why("param_quant") and "q8" in p.why("param_quant")


def test_param_quant_explicit_window_override_wins():
    p = plan_run(FULL, TRAIN_4K, _NVME_HW,
                 overrides={"param_quant": "q8", "prefetch_layers": 3})
    assert p.prefetch_layers == 3


def test_param_quant_off_nvme_warns_no_effect():
    hw = HardwareSpec(n_devices=16, device_mem=32e9, host_mem=1.5e12,
                      nvme_capacity=28e12)
    p = plan_run(FULL, TRAIN_4K, hw, overrides={"param_quant": "q8"})
    assert p.param_tier == "device"
    assert any("param_quant" in w and "no effect" in w for w in p.warnings)
    assert p.predictions.get("param_compression_ratio", 1.0) == 1.0


def test_param_quant_invalid_value_raises():
    with pytest.raises(ValueError, match="param_quant"):
        plan_run(FULL, TRAIN_4K, _NVME_HW, overrides={"param_quant": "q2"})


def test_param_quant_roundtrips_json_and_run_config():
    p = plan_run(FULL, TRAIN_4K, _NVME_HW, overrides={"param_quant": "q4"})
    assert InfinityPlan.from_json(p.to_json()) == p
    rc = p.to_run_config(nvme_dir="/tmp/x")
    assert rc.offload.param_quant == "q4"
    assert "quant=q4" in p.summary()
    assert "param_quant" in OVERRIDABLE


def test_override_zero3_family_feasibility():
    """zero3 runs dense and moe families only — and a MoE override without
    NVMe-resident params has no all-resident explicit path to fall back to
    (expert rows exist only as paged schedule units)."""
    ssm = configs.get("mamba2-370m")
    with pytest.raises(ValueError, match="dense/moe only"):
        plan_run(ssm, TRAIN_4K, HardwareSpec(), overrides={"engine": "zero3"})
    moe = configs.get("granite-moe-1b-a400m")
    with pytest.raises(ValueError, match="param_tier='nvme'"):
        plan_run(moe, TRAIN_4K, HardwareSpec(), overrides={"engine": "zero3"})
    # the pairing that works: zero3 + NVMe params plans cleanly
    p = plan_run(moe, TRAIN_4K, _NVME_HW,
                 overrides={"engine": "zero3", "param_tier": "nvme"})
    assert p.engine == "zero3" and p.param_tier == "nvme"
    assert p.predictions["expert_peak_resident_bytes"] > 0


# ---------------------------------------------------------------------------
# round-trips: JSON and to_run_config -> re-plan stability
# ---------------------------------------------------------------------------


def test_plan_json_roundtrip():
    hw = HardwareSpec(n_devices=16, device_mem=1e9, host_mem=8e9,
                      nvme_capacity=28e12)
    p = plan_run(FULL, TRAIN_4K, hw)
    p2 = InfinityPlan.from_json(p.to_json())
    assert p2 == p
    # the serialized form is valid JSON with the version stamp
    assert json.loads(p.to_json())["plan_version"] == 1


def test_plan_lowering_and_replan_stability():
    hw = HardwareSpec(n_devices=16, device_mem=1e9, host_mem=8e9,
                      nvme_capacity=28e12)
    p = plan_run(FULL, TRAIN_4K, hw)
    rc = p.to_run_config(nvme_dir="/tmp/x")
    assert rc.parallel.engine == p.engine
    assert rc.offload.param_tier == p.param_tier
    assert rc.offload.prefetch_layers == p.prefetch_layers
    assert rc.offload.pinned_buffer_mb == p.pinned_buffer_mb
    assert rc.parallel.grad_accum == p.grad_accum
    # planning is deterministic: same inputs -> identical plan and lowering
    p2 = plan_run(FULL, TRAIN_4K, hw)
    assert p2 == p
    assert p2.to_run_config(nvme_dir="/tmp/x") == rc


def test_plan_save_load(tmp_path):
    p = plan_run(FULL, TRAIN_4K, HardwareSpec(n_devices=4, device_mem=32e9,
                                              host_mem=256e9))
    path = str(tmp_path / "plan.json")
    p.save(path)
    assert InfinityPlan.load(path) == p


def test_string_model_and_shape_resolve():
    p = plan_run("smollm-135m", "train_4k",
                 HardwareSpec(n_devices=16, device_mem=32e9, host_mem=1e12))
    assert p.model.arch == "smollm-135m"
    assert p.shape.name == "train_4k"


# ---------------------------------------------------------------------------
# predicted vs measured: a real executor step under each lowered config
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_env():
    mesh = make_local_mesh(1, 1)
    cfg = dataclasses.replace(configs.smoke("smollm-135m"), n_layers=4)
    # act-heavy shape (checkpoints >> 2N): a host-dominant placement is
    # only transit-feasible when the device pressure came from activations
    shape = ShapeConfig("plan-smoke", 512, 4, "train")
    batch = {"tokens": jnp.ones((4, 512), jnp.int32),
             "labels": jnp.ones((4, 512), jnp.int32)}
    return mesh, cfg, shape, batch


def _measure(plan, mesh, batch, nvme_dir, steps=2):
    run = plan.to_run_config(train=TrainConfig(lr=3e-3, warmup_steps=2),
                             nvme_dir=str(nvme_dir))
    ex = InfinityExecutor(run, mesh, plan=plan)
    state = ex.init_state(jax.random.PRNGKey(0))
    step = ex.make_train_step()
    metrics = {}
    for _ in range(steps):
        state, metrics = step(state, batch)
    return ex, metrics


def test_predicted_peak_bounds_measured_all_scenarios(smoke_env, tmp_path):
    """The acceptance inequality: for device-, host-, and nvme-dominant
    plans, predicted ``peak_resident_param_bytes`` >= what a real executor
    step measures under the lowered config."""
    mesh, cfg, shape, batch = smoke_env
    sb = state_bytes(cfg, shape, 1)
    total = sb.states_total + sb.act_bytes("none")
    # starved HBM: big enough for the 2N param transit, too small for the
    # Eq. 3 checkpoints (so every class demotes and acts go host)
    starved_dev = (sb.param + sb.act_ckpt) / 2 / 0.7
    scenarios = {
        # roomy: everything fits on device with margin
        "device": HardwareSpec(n_devices=1, device_mem=4 * total,
                               host_mem=100 * total,
                               nvme_capacity=100 * total),
        # HBM-starved, big DRAM: states demote to host
        "host": HardwareSpec(n_devices=1, device_mem=starved_dev,
                             host_mem=100 * total, nvme_capacity=100 * total),
        # HBM- and DRAM-starved: states demote to NVMe
        "nvme": HardwareSpec(n_devices=1, device_mem=starved_dev,
                             host_mem=sb.param * 2.5,
                             nvme_capacity=100 * total),
    }
    for dominant, hw in scenarios.items():
        plan = plan_run(cfg, shape, hw)
        assert plan.feasible, (dominant, plan.warnings)
        assert plan.param_tier == dominant, (dominant, plan.summary())
        if dominant == "nvme":
            assert plan.engine == "zero3"
            assert plan.grad_tier == "nvme" and plan.opt_tier == "nvme"
        ex, m = _measure(plan, mesh, batch, tmp_path / dominant)
        pred = plan.predictions["peak_resident_param_bytes"]
        measured = m.get("peak_resident_param_bytes")
        if measured is not None:
            assert 0 < measured <= pred, (dominant, measured, pred)
            # the executor's cross-check reports the same verdict in-band
            assert m["plan_peak_resident_param_bytes"] == pred
            assert m["plan_residency_ok"]
            # the predicted denominator matches the executor's streamed set
            # (block rows on zero3 — not the whole-model byte count)
            assert plan.predictions["param_total_bytes"] == \
                ex.total_param_bytes
        else:
            # in-graph tiers: nothing streams, full residency predicted
            assert pred == sb.param
        assert np.isfinite(float(m["loss"]))


def test_executor_crosscheck_reports_step_bytes(smoke_env, tmp_path):
    mesh, cfg, shape, batch = smoke_env
    sb = state_bytes(cfg, shape, 1)
    hw = HardwareSpec(n_devices=1,
                      device_mem=(sb.param + sb.act_ckpt) / 2 / 0.7,
                      host_mem=sb.param * 2.5, nvme_capacity=1e12)
    plan = plan_run(cfg, shape, hw)
    _, m = _measure(plan, mesh, batch, tmp_path / "xc")
    assert m["plan_efficiency"] == plan.predictions["efficiency"]
    assert m["plan_opt_step_bytes"] == (
        plan.predictions["opt_step_read_bytes"]
        + plan.predictions["opt_step_write_bytes"])


# ---------------------------------------------------------------------------
# HardwareSpec detection / validation
# ---------------------------------------------------------------------------


def test_detect_probes_live_backend(tmp_path):
    hw = HardwareSpec.detect(nvme_dir=str(tmp_path))
    assert hw.source == "detected"
    assert hw.n_devices == len(jax.devices())
    assert hw.device_mem > 0 and hw.host_mem > 0
    assert hw.nvme_capacity > 0  # tmp_path's filesystem has free space
    # explicit overrides win over probed values
    hw2 = HardwareSpec.detect(nvme_dir=str(tmp_path), device_mem=123.0,
                              n_devices=7)
    assert hw2.device_mem == 123.0 and hw2.n_devices == 7


def test_detect_takes_accelerator_rates_from_peaks_table(monkeypatch, tmp_path):
    """On an accelerator, detect() reads peak FLOP/s, HBM bandwidth and (when
    the backend reports none) HBM bytes from repro.peaks; a device kind
    missing from the table raises instead of defaulting."""
    from repro import peaks

    class _Chip:
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind

        def memory_stats(self):
            return None

    monkeypatch.setattr(jax, "devices", lambda: [_Chip("TPU v5 lite")])
    hw = HardwareSpec.detect(nvme_dir=str(tmp_path))
    assert (hw.peak_flops, hw.device_bw, hw.device_mem) == (197e12, 819e9, 16e9)
    monkeypatch.setattr(jax, "devices", lambda: [_Chip("TPU v99")])
    with pytest.raises(ValueError, match="TPU v99"):
        HardwareSpec.detect(nvme_dir=str(tmp_path))
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for(_Chip("TPU v99"))
    monkeypatch.undo()
    assert peaks.peaks_for(jax.devices()[0]) is None  # the CPU: no device peaks


def test_hardware_spec_validation():
    with pytest.raises(ValueError, match="n_devices"):
        HardwareSpec(n_devices=0)
    with pytest.raises(ValueError, match="host_mem"):
        HardwareSpec(host_mem=-1.0)
    with pytest.raises(ValueError, match="working_mem_fraction"):
        HardwareSpec(working_mem_fraction=0.0)
    with pytest.raises(ValueError, match="unknown tier"):
        HardwareSpec().tier_capacity("floppy")


# ---------------------------------------------------------------------------
# satellite: ValueError (not assert) config validation
# ---------------------------------------------------------------------------


def test_offload_config_rejects_bad_tier_with_valueerror():
    with pytest.raises(ValueError, match=r"param_tier='tape'.*device"):
        OffloadConfig(param_tier="tape")
    with pytest.raises(ValueError, match=r"act_tier='nvme'"):
        OffloadConfig(act_tier="nvme")
    with pytest.raises(ValueError, match=r"param_read_ahead=0.*>= 1"):
        OffloadConfig(param_read_ahead=0)


def test_parallel_config_rejects_bad_values_with_valueerror():
    with pytest.raises(ValueError, match=r"engine='tpu'.*pjit"):
        ParallelConfig(engine="tpu")
    with pytest.raises(ValueError, match=r"zero_stage=7"):
        ParallelConfig(zero_stage=7)
    with pytest.raises(ValueError, match=r"remat='half'"):
        ParallelConfig(remat="half")


def test_make_offload_positional_tier_deprecated():
    with pytest.warns(DeprecationWarning, match="OPTIMIZER tier"):
        off = make_offload("nvme")
    assert off.opt_tier == "nvme"
    # the keyword spelling is silent
    import warnings as w

    with w.catch_warnings():
        w.simplefilter("error")
        off = make_offload(opt_tier="host", param_tier="nvme")
    assert off.opt_tier == "host" and off.param_tier == "nvme"
    with pytest.raises(ValueError, match="not both"):
        make_offload("nvme", opt_tier="host")


# ---------------------------------------------------------------------------
# satellite: default_prefetch_layers edge cases
# ---------------------------------------------------------------------------


def test_default_prefetch_layers_single_layer_model():
    assert default_prefetch_layers(1, 1 << 20, 1024) == 1


def test_default_prefetch_layers_never_admits_full_residency():
    # even at pathological bandwidth the window stays < num_layers
    for bw in (1e3, 1e6, 1e9):
        w = default_prefetch_layers(8, 1 << 24, 1, slow_bw=bw)
        assert 1 <= w <= 7


def test_default_prefetch_layers_zero_bandwidth_spec():
    """A zero-bandwidth hardware spec must not divide by zero: the guard
    floors the rate at 1 B/s and the clamp still bounds the window."""
    w = default_prefetch_layers(12, 1 << 20, 4096, slow_bw=0.0)
    assert 1 <= w <= 11
    p = plan_run(FULL, TRAIN_4K,
                 HardwareSpec(n_devices=16, device_mem=1e9, host_mem=8e9,
                              nvme_capacity=28e12, nvme_bw=0.0))
    assert 1 <= p.prefetch_layers < FULL.n_layers


def test_layer_schedule_window_exceeding_layers_clamps():
    sched = LayerSchedule(3, window=99)
    assert sched.window == 3
    events = sched.forward()
    assert sum(e.op == "use" for e in events) == 3


def test_auto_window_override_resolves_at_plan_time():
    """A plan never lowers prefetch_layers=0: the runtime's auto-resolution
    uses paper-nominal rates, not this plan's HardwareSpec, so the window
    is pinned at plan time and prediction == lowered config."""
    hw = HardwareSpec(n_devices=16, device_mem=1e9, host_mem=8e9,
                      nvme_capacity=28e12)
    p = plan_run(FULL, TRAIN_4K, hw, overrides={"prefetch_layers": 0})
    assert p.prefetch_layers >= 1
    assert p.param_tier == "nvme"
    assert any("resolved to" in w for w in p.warnings)
    assert p.to_run_config().offload.prefetch_layers == p.prefetch_layers


def test_plan_window_override_at_or_above_layers_warns():
    hw = HardwareSpec(n_devices=16, device_mem=1e9, host_mem=8e9,
                      nvme_capacity=28e12)
    p = plan_run(FULL, TRAIN_4K, hw,
                 overrides={"prefetch_layers": FULL.n_layers})
    assert any("full residency" in w for w in p.warnings)


def test_overridable_covers_every_legacy_knob():
    """Every knob the ISSUE names must be expressible as a plan override."""
    for field in ("engine", "param_tier", "grad_tier", "opt_tier",
                  "prefetch_layers", "read_ahead", "nvme_workers",
                  "pinned_buffer_mb", "remat", "grad_accum"):
        assert field in OVERRIDABLE


# ---------------------------------------------------------------------------
# serving: KV-tier planning
# ---------------------------------------------------------------------------


def test_plan_serving_roomy_keeps_kv_on_device():
    shape = ShapeConfig("serve", 128, 16, "decode")
    hw = HardwareSpec(n_devices=1, device_mem=64e9, host_mem=64e9)
    p = plan_run(FULL, shape, hw)
    assert p.kv_tier == "device" and p.kv_slots == 16
    assert p.kv_block_tokens >= 16 and p.kv_prefetch_blocks >= 1
    assert p.predictions["kv_resident_bytes"] == pytest.approx(
        16 * p.predictions["kv_per_seq_bytes"])
    assert p.predictions["kv_parked_bytes"] == 0
    assert "kv=" in p.summary()


def test_plan_serving_starved_device_pages_kv_to_host():
    from repro.core import kvcache

    shape = ShapeConfig("serve", 128, 16, "decode")
    per = kvcache.sequence_kv_bytes(FULL, 128)
    sb = state_bytes(FULL, shape, 1)
    # room for params + a few sequences only: KV overflow must park on host
    hw = HardwareSpec(n_devices=1,
                      device_mem=(sb.param + 4 * per) / 0.7,
                      host_mem=64e9)
    p = plan_run(FULL, shape, hw)
    assert p.kv_tier == "host"
    assert 1 <= p.kv_slots < 16
    assert p.predictions["kv_parked_bytes"] == pytest.approx(
        (16 - p.kv_slots) * per)
    assert p.predictions["kv_resident_bytes"] < 16 * per


def test_plan_serving_kv_fields_roundtrip_json_and_overrides():
    shape = ShapeConfig("serve", 64, 8, "decode")
    hw = HardwareSpec(n_devices=1, device_mem=32e9, host_mem=64e9)
    p = plan_run(FULL, shape, hw,
                 overrides={"kv_tier": "host", "kv_slots": 3,
                            "kv_block_tokens": 32})
    assert (p.kv_tier, p.kv_slots, p.kv_block_tokens) == ("host", 3, 32)
    p2 = InfinityPlan.from_json(p.to_json())
    assert (p2.kv_tier, p2.kv_slots, p2.kv_block_tokens,
            p2.kv_prefetch_blocks) == (p.kv_tier, p.kv_slots,
                                       p.kv_block_tokens, p.kv_prefetch_blocks)
    assert p2.predictions["kv_resident_bytes"] == \
        p.predictions["kv_resident_bytes"]
    with pytest.raises(ValueError):
        plan_run(FULL, shape, hw, overrides={"kv_tier": "floppy"})


def test_plan_train_shapes_skip_kv_planning():
    hw = HardwareSpec(n_devices=16, device_mem=32e9, host_mem=1.5e12)
    p = plan_run(FULL, TRAIN_4K, hw)
    assert p.kv_slots == 0
    assert "kv_resident_bytes" not in p.predictions
    assert "kv=" not in p.summary()
