"""FLOP counts and the peaks table, against hand counts. The counts are the
reference's (``perfbench/references/decoder.py``), the harness's model FLOPs
six times its matmul weights plus its attention."""
import json

import pytest
import tiny

from perfbench import harness, peaks

CONFIGS = tiny.REPO / "perfbench" / "configs"
SMOLLM = json.loads((CONFIGS / "smollm-135m.json").read_text())
DECODER = harness.load_module(tiny.REPO / "perfbench/references/decoder.py")
# granite-3.0-1b-a400m-base at its published widths: no cell runs it yet,
# but the MoE terms of the count are checked on it
GRANITE = {"hidden_size": 1024, "intermediate_size": 512,
           "num_hidden_layers": 24, "num_attention_heads": 16,
           "num_key_value_heads": 8, "head_dim": 64, "vocab_size": 49155,
           "num_local_experts": 32, "num_experts_per_tok": 8}


def test_smollm_params_by_hand():
    # per layer: q,o 576x576 each; k,v 576x192 each; SwiGLU 3 x 576x1536;
    # two 576-wide norm gains. Plus the tied 49152x576 embedding and ln_f.
    layer = 2 * 576 * 576 + 2 * 576 * 192 + 3 * 576 * 1536 + 2 * 576
    total = 30 * layer + 49152 * 576 + 576
    counts = DECODER.param_count(SMOLLM)
    assert counts["total"] == counts["active"] == total
    assert abs(total - 134.5e6) < 0.1e6


def test_granite_params_by_hand():
    attn = 2 * 1024 * 1024 + 2 * 1024 * 512
    experts = 32 * 3 * 1024 * 512
    layer = attn + 1024 * 32 + 2 * 1024
    embed = 49155 * 1024 + 1024
    counts = DECODER.param_count(GRANITE)
    assert counts["total"] == 24 * (layer + experts) + embed
    assert counts["active"] == 24 * (layer + experts // 4) + embed
    assert abs(counts["total"] - 1.334e9) < 0.005e9
    assert abs(counts["active"] - 0.43e9) < 0.01e9


@pytest.mark.parametrize("cfg,matmul_params,attn", [
    # attention: 3 passes x 30 layers x 2 matmuls x 2 FLOPs x 9 heads x 64
    # x 1024.5 mean causal keys
    (SMOLLM, 30 * (2 * 576 * 576 + 2 * 576 * 192 + 3 * 576 * 1536)
     + 576 * 49152, 3 * 30 * 4 * 9 * 64 * 1024.5),
    (GRANITE, 24 * (2 * 1024 * 1024 + 2 * 1024 * 512 + 1024 * 32
                    + 8 * 3 * 1024 * 512) + 1024 * 49155,
     3 * 24 * 4 * 16 * 64 * 1024.5),
])
def test_model_flops_per_token_by_hand(cfg, matmul_params, attn):
    assert DECODER.matmul_params_per_token(cfg) == matmul_params
    assert DECODER.attention_flops_per_token(cfg, 2048) == pytest.approx(attn)
    assert harness.model_flops_per_token(DECODER, cfg, 2048) == pytest.approx(
        6 * matmul_params + attn)


def test_model_flops_near_published_estimates():
    assert harness.model_flops_per_token(DECODER, SMOLLM, 2048) == pytest.approx(
        1.02e9, rel=0.01)
    assert harness.model_flops_per_token(DECODER, GRANITE, 2048) == pytest.approx(
        2.87e9, rel=0.01)


class Counts:
    """A reference's counts and nothing else: the harness knows no model."""

    @staticmethod
    def matmul_params_per_token(cfg):
        return cfg["weights"]

    @staticmethod
    def attention_flops_per_token(cfg, seq_len):
        return cfg["per_key"] * seq_len


def test_model_flops_come_from_the_reference():
    assert harness.model_flops_per_token(
        Counts, {"weights": 7, "per_key": 3.0}, 10) == 6 * 7 + 30.0


def test_the_smollm_cell_counts_by_the_reference_its_configuration_names():
    cell = harness.load_cell(tiny.REPO, "smollm-train-hbm")
    ref = harness.reference_module(cell)
    assert ref.__file__.endswith("/references/decoder.py")
    assert harness.model_flops_per_token(
        ref, cell.config, cell.traffic["seq_len"]) == pytest.approx(
        6 * (30 * (2 * 576 * 576 + 2 * 576 * 192 + 3 * 576 * 1536)
             + 576 * 49152) + 3 * 30 * 4 * 9 * 64 * 1024.5)


def test_peaks_table():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9, 16e9)
    assert "Google Cloud" in v5e.source
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
