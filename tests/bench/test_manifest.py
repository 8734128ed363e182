"""BENCHMARK.json and the files it names (CPU; nothing touches a TPU)."""
import copy
import dataclasses
import json
import os
import re
import subprocess
import sys
import time

import jax
import pytest
import tiny

from perfbench import harness

REPO = tiny.REPO
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_names_and_units():
    names = ([m["name"] for m in METRICS]
             + [w["name"] for w in BENCH["workloads"]]
             + [c["name"] for c in BENCH["configs"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in METRICS)
    for group in (METRICS, BENCH["workloads"], BENCH["configs"]):
        assert len({x["name"] for x in group}) == len(group)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_found_by_name(workload):
    cell = harness.load_cell(REPO, workload)
    assert cell.config["name"] == cell.workload["config"]
    assert (REPO / "perfbench" / "references" /
            f"{cell.config['reference']}.py").exists()
    for section in ("end_to_end", "per_layer"):
        for m in cell.metrics(section):
            reader = harness.load_module(
                REPO / "perfbench" / "metrics" / f"{m['name']}.py")
            assert callable(reader.read)
    e2e = {m["name"] for m in cell.metrics("end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.metrics("per_layer")


def test_each_per_layer_metric_moves_what_its_cells_report():
    for m in BENCH["per_layer"]:
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        for w in m.get("workloads", [x["name"] for x in BENCH["workloads"]]):
            assert "workloads" not in moved or w in moved["workloads"], (
                m["name"], w)


def test_a_cell_added_as_new_files_is_picked_up(tmp_path):
    """A later PR adds a configuration, a traffic mix, limits and a metric
    as files, plus entries in BENCHMARK.json; no existing file changes."""
    root = tiny.make_root(tmp_path)
    before = {p: p.read_bytes() for p in (REPO / "perfbench").rglob("*.py")}
    program = dict(tiny.DENSE["program"],
                   fields=dict(tiny.DENSE["program"]["fields"], d_model=96))
    cfg = dict(tiny.DENSE, name="tiny-dense-wide", hidden_size=96,
               program=program)
    (root / "perfbench/configs/tiny-dense-wide.json").write_text(
        json.dumps(cfg))
    (root / "perfbench/traffic/long.json").write_text(
        json.dumps(tiny.traffic(seq=128)))
    (root / "perfbench/limits/new-cell.json").write_text(
        json.dumps(tiny.real_limits("smollm-train-hbm")))
    (root / "perfbench/metrics/steps_per_s.py").write_text(
        "def read(ctx):\n    return ctx['steps'] / ctx['window_s']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-dense-wide", "source": "test",
                             "file": "perfbench/configs/tiny-dense-wide.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "new-cell", "config": "tiny-dense-wide",
                               "traffic": "long", "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "steps_per_s", "unit": "1/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell(root, "new-cell")
    assert cell.config["hidden_size"] == 96
    assert cell.traffic["seq_len"] == 128
    names = [m["name"] for m in cell.metrics("end_to_end")]
    assert "steps_per_s" in names
    reader = harness.load_module(root / "perfbench/metrics/steps_per_s.py")
    assert reader.read({"steps": 10, "window_s": 5.0}) == 2.0
    assert harness.program_model(cell.config).d_model == 96
    after = {p: p.read_bytes() for p in (REPO / "perfbench").rglob("*.py")}
    assert before == after


# an architecture the benchmark does not hold: an MoE decoder with an untied
# head, its configuration in keys of its own, its weights in a layout of its
# own (tests/bench/untied_reference.py)
UNTIED = {
    "name": "tiny-untied-moe", "source": "test",
    "reference": "untied_reference", "vocab_size": 131, "reduced": [],
    "d_model": 64, "n_layers": 2, "n_heads": 4, "n_kv_heads": 2,
    "d_head": 16, "d_expert": 32, "n_experts": 8, "top_k": 2,
    "router_group": 1024, "capacity_factor": 1.5, "rope_theta": 10000.0,
    "norm_eps": 1e-6,
    "program": {
        "arch": "granite-moe-1b-a400m",
        "fields": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                   "n_kv_heads": 2, "head_dim": 16, "d_ff": 32,
                   "vocab_size": 131, "tie_embeddings": False,
                   "n_experts": 8, "top_k": 2, "capacity_factor": 1.5},
        "fixed": {"norm_eps": 1e-6, "router_group": 1024},
        "leaves": {
            "tok_embeddings": {"path": "embed/tok", "pad": [0]},
            "lm_head": {"path": "embed/unembed", "pad": [1]},
            "final_norm": {"path": "ln_f/scale"},
            **{k: {"path": f"blocks/{v}", "stacked": True} for k, v in {
                "attn_norm": "ln1/scale", "ffn_norm": "ln2/scale",
                "q_proj": "attn/wq", "k_proj": "attn/wk",
                "v_proj": "attn/wv", "o_proj": "attn/wo",
                "gate": "moe/router", "experts_gate": "moe/w_gate",
                "experts_up": "moe/w_in", "experts_down": "moe/w_out"}.items()},
        }}}


def untied_root(tmp_path, cfg=UNTIED):
    """A checkout with the untied cell, its reference and a reader of the
    model FLOPs the harness counts, all as new files."""
    root = tiny.make_root(tmp_path, cells={
        "untied-cell": (cfg, tiny.traffic(), 1)}, limits=tiny.MOE_LIMITS)
    (root / "perfbench/references/untied_reference.py").write_bytes(
        (REPO / "tests/bench/untied_reference.py").read_bytes())
    (root / "perfbench/metrics/model_flops_per_token.py").write_text(
        "def read(ctx):\n    return ctx['model_flops_per_token']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["end_to_end"].append({"name": "model_flops_per_token",
                                "unit": "FLOP", "better": "lower",
                                "bound": 0.01, "source": "host_clock"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_an_architecture_added_as_new_files_trains_correct(tmp_path,
                                                          monkeypatch):
    """Its configuration, reference, traffic and limits are new files; the
    harness runs it to ``correct`` true and counts its FLOPs by its own
    reference (decoder.py's counts could not read this configuration)."""
    monkeypatch.setattr(harness, "enable_cache", lambda root: None)
    before = {p: p.read_bytes() for p in (REPO / "perfbench").rglob("*.py")}
    root = untied_root(tmp_path)
    result = harness.run(root, "untied-cell", 2**31 + 11, 0.5, False,
                         t_start=time.perf_counter(),
                         devices=jax.devices()[:1])
    assert result["correct"], result["compared"]
    # per token: (q, k, v, o + router + 2 of 8 experts) x 2 layers + head,
    # 6 FLOPs each; attention 3 x 2 layers x 2 matmuls x 2 x 4 heads x 16
    # x 32.5 mean causal keys
    matmul = 2 * (64 * 16 * 12 + 64 * 8 + 2 * 3 * 64 * 32) + 64 * 131
    attention = 3 * 2 * 2 * 2 * 4 * 16 * 32.5
    assert result["metrics"]["model_flops_per_token"]["value"] == (
        6 * matmul + attention)
    decoder = harness.load_module(REPO / "perfbench/references/decoder.py")
    with pytest.raises(KeyError):
        decoder.matmul_params_per_token(UNTIED)
    assert before == {p: p.read_bytes()
                      for p in (REPO / "perfbench").rglob("*.py")}


def no_path_for_the_head(cfg):
    del cfg["program"]["leaves"]["lm_head"]
    return "lm_head"


def no_leaf_for_the_programs_head(cfg):
    """decoder.py's tied weights into a program with an untied head."""
    cfg.clear()
    cfg.update(copy.deepcopy(tiny.DENSE))
    cfg["program"]["fields"]["tie_embeddings"] = False
    return "embed/unembed"


def head_padded_along_another_axis(cfg):
    cfg["program"]["leaves"]["lm_head"]["pad"] = [0]
    return "lm_head"


@pytest.mark.parametrize("fault", [no_path_for_the_head,
                                   no_leaf_for_the_programs_head,
                                   head_padded_along_another_axis])
def test_an_unmapped_leaf_is_an_error_naming_it(tmp_path, fault):
    cfg = copy.deepcopy(UNTIED)
    named = fault(cfg)
    root = untied_root(tmp_path, cfg)
    cell = harness.load_cell(root, "untied-cell")
    with pytest.raises(SystemExit, match=named):
        harness.Program(cell, jax.devices()[:1],
                        harness.reference_module(cell))


def published_model(cfg):
    """The ModelConfig that the published Llama/Granite keys describe, as
    the harness mapped them before configurations brought a program block."""
    from repro import configs

    return dataclasses.replace(
        configs.get(cfg["program"]["arch"]), n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        n_experts=cfg.get("num_local_experts", 0),
        top_k=cfg.get("num_experts_per_tok", 0),
        capacity_factor=cfg.get("capacity_factor", 1.25))


@pytest.mark.parametrize("cfg", [
    json.loads((REPO / c["file"]).read_text()) for c in BENCH["configs"]
] + [tiny.DENSE, tiny.MOE], ids=lambda c: c["name"])
def test_program_block_gives_the_model_of_the_published_keys(cfg):
    assert harness.program_model(cfg) == published_model(cfg)


@pytest.mark.parametrize("change,refusal", [
    (lambda c: c["program"]["fields"].update(n_layer=2), "no field n_layer"),
    (lambda c: c.update(rms_norm_eps=1e-5), "runs rms_norm_eps=1e-06"),
    (lambda c: c.update(published={"rms_norm_eps": 1e-5}),
     "list rms_norm_eps in 'reduced'"),
])
def test_program_model_refuses(change, refusal):
    cfg = copy.deepcopy(tiny.DENSE)
    change(cfg)
    with pytest.raises(SystemExit, match=refusal):
        harness.program_model(cfg)
    cfg["reduced"] = ["rms_norm_eps"]
    if "published" in cfg:  # a cut that is listed is run
        assert harness.program_model(cfg).n_layers == 2


def test_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable] + BENCH["command"][1:] + [
        "--workload", BENCH["workloads"][0]["name"], "--seed", str(2**31 + 7),
        "--seconds", "1", "--trace", "0"]
    out = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert "cpu" in out.stderr.lower()
    assert out.stdout.strip() == ""


def test_command_and_paths_stay_inside_the_benchmark():
    assert BENCH["paths"] == ["perfbench", "tests/bench"]
    assert all(not w.startswith("/") and ".." not in w
               for w in BENCH["command"])
    for c in BENCH["configs"]:
        assert c["file"].startswith("perfbench/")
