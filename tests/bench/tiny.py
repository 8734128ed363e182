"""A checkout-shaped directory holding tiny cells, for runs on the CPU.

The harness code is the real one; only the data files are small: the
program's own smoke sizes (``repro.configs.smoke``), a 64-token sequence.
"""
from __future__ import annotations

import json
import os
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def leaves(mlp: str, router: bool = False) -> dict:
    """``program.leaves`` of ``decoder.py``'s weights in the program's tree:
    the dense block's MLP under ``blocks/mlp``, an MoE block's under
    ``blocks/moe`` with its router."""
    out = {"embed": {"path": "embed/tok", "pad": [0]},
           "ln_f": {"path": "ln_f/scale"}}
    layer = {"ln1": "ln1/scale", "ln2": "ln2/scale", "wq": "attn/wq",
             "wk": "attn/wk", "wv": "attn/wv", "wo": "attn/wo",
             "w_gate": f"{mlp}/w_gate", "w_up": f"{mlp}/w_in",
             "w_down": f"{mlp}/w_out"}
    if router:
        layer["router"] = f"{mlp}/router"
    out.update({k: {"path": f"blocks/{v}", "stacked": True}
                for k, v in layer.items()})
    return out


FIXED = {"hidden_act": "silu", "rms_norm_eps": 1e-6,
         "embedding_multiplier": 1.0, "residual_multiplier": 1.0,
         "logits_scaling": 1.0, "attention_multiplier": 0.25}
DENSE = {"name": "tiny-dense", "source": "test", "reference": "decoder",
         "hidden_size": 48, "intermediate_size": 128,
         "num_hidden_layers": 2, "num_attention_heads": 3,
         "num_key_value_heads": 1, "head_dim": 16, "vocab_size": 128,
         "hidden_act": "silu", "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
         "tie_word_embeddings": True, "num_local_experts": 0,
         "num_experts_per_tok": 0,
         "program": {"arch": "smollm-135m",
                     "fields": {"n_layers": 2, "d_model": 48, "n_heads": 3,
                                "n_kv_heads": 1, "head_dim": 16, "d_ff": 128,
                                "vocab_size": 128, "rope_theta": 10000.0,
                                "tie_embeddings": True, "n_experts": 0,
                                "top_k": 0, "capacity_factor": 1.25},
                     "fixed": FIXED, "leaves": leaves("mlp")}}
MOE = dict(DENSE, name="tiny-moe", hidden_size=64, intermediate_size=32,
           num_attention_heads=4, num_key_value_heads=2, vocab_size=131,
           num_local_experts=8, num_experts_per_tok=2, capacity_factor=1.5,
           router_group=1024,
           program={"arch": "granite-moe-1b-a400m",
                    "fields": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                               "n_kv_heads": 2, "head_dim": 16, "d_ff": 32,
                               "vocab_size": 131, "rope_theta": 10000.0,
                               "tie_embeddings": True, "n_experts": 8,
                               "top_k": 2, "capacity_factor": 1.5},
                    "fixed": dict(FIXED, router_group=1024),
                    "leaves": leaves("moe", router=True)})
# No MoE cell has limits read on the chip yet. These keep the dense cell's
# grad and update limits; the loss limit is wider, since the program's
# router takes bf16 logits and the tiny MoE's loss gap on the CPU is 3e-5.
MOE_LIMITS = {"loss_gap": {"limit": 1e-4}, "grad_gap": {"limit": 0.01},
              "update_gap": {"limit": 0.3}}
OPT = {"lr": 3e-4, "beta1": 0.9, "beta2": 0.95, "eps": 1e-8,
       "weight_decay": 0.1, "warmup_steps": 10}


def traffic(opt_tier="device", data_mesh=1, batch=4, seq=64):
    return {"why": "test", "engine": "pjit", "zero_stage": 3,
            "data_mesh": data_mesh,
            "tiers": {"param": "device", "grad": "device", "opt": opt_tier},
            "seq_len": seq, "global_batch": batch, "tokens": "uniform",
            "loop": "closed", "optimizer": OPT}


def real_limits(workload: str) -> dict:
    return json.loads((REPO / "perfbench" / "limits" /
                       f"{workload}.json").read_text())


def make_root(tmp: Path, cells=None, limits=None) -> Path:
    """A directory laid out like a checkout: tiny data, the real code. Each
    cell is held to ``limits``, by default the dense benchmark cell's or,
    for MoE, ``MOE_LIMITS``."""
    (tmp / "perfbench").mkdir(parents=True)
    for sub in ("metrics", "references"):  # new files here stay in ``tmp``
        (tmp / "perfbench" / sub).mkdir()
        for f in (REPO / "perfbench" / sub).glob("*.py"):
            os.symlink(f, tmp / "perfbench" / sub / f.name)
    os.symlink(REPO / "src", tmp / "src")
    for sub in ("configs", "traffic", "limits"):
        (tmp / "perfbench" / sub).mkdir()
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"], bench["workloads"] = [], []
    cells = cells or {"tiny-dense-hbm": (DENSE, traffic(), 1),
                      "tiny-moe-dp": (MOE, traffic(), 1)}
    for name, (cfg, tr, chips) in cells.items():
        cpath = f"perfbench/configs/{cfg['name']}.json"
        if not (tmp / cpath).exists():
            (tmp / cpath).write_text(json.dumps(cfg))
            bench["configs"].append({"name": cfg["name"], "source": "test",
                                     "file": cpath, "reduced": [],
                                     "why": "test"})
        (tmp / "perfbench" / "traffic" / f"{name}.json").write_text(
            json.dumps(tr))
        family = (MOE_LIMITS if cfg.get("num_local_experts")
                  else real_limits("smollm-train-hbm"))
        (tmp / "perfbench" / "limits" / f"{name}.json").write_text(
            json.dumps(limits or family))
        bench["workloads"].append({"name": name, "config": cfg["name"],
                                   "traffic": name, "chips": chips,
                                   "why": "test"})
    for section in ("end_to_end", "per_layer"):
        for m in bench[section]:
            m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
