"""BENCHMARK.json and the files it names (CPU; nothing touches a TPU)."""
import json
import os
import re
import subprocess
import sys

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
    cfg = dict(tiny.DENSE, name="tiny-dense-wide", hidden_size=96)
    (root / "perfbench/configs/tiny-dense-wide.json").write_text(
        json.dumps(cfg))
    (root / "perfbench/traffic/long.json").write_text(
        json.dumps(tiny.traffic(seq=128)))
    (root / "perfbench/limits/new-cell.json").write_text(
        json.dumps(tiny.real_limits("smollm-train-hbm")))
    metric_dir = tmp_path / "metrics"
    metric_dir.mkdir()
    for f in (REPO / "perfbench" / "metrics").iterdir():
        os.symlink(f, metric_dir / f.name)
    (metric_dir / "steps_per_s.py").write_text(
        "def read(ctx):\n    return ctx['steps'] / ctx['window_s']\n")
    os.unlink(root / "perfbench" / "metrics")
    os.symlink(metric_dir, root / "perfbench" / "metrics")
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
