"""FLOPs and bytes of one step against a hand count, and the peaks table."""
import pytest

from bench import costs
from bench.tests.support import reduce_model, ROOT
import json


def _models():
    return {m["arch"]: reduce_model(m) for m in json.loads((ROOT / "bench/configs/trio-1.5x.json").read_text())["models"]}


def test_qwen3_reduced_by_hand():
    m = _models()["qwen3-1.7b"]
    d, L, H, G, hd, F, V = 128, 2, 4, 2, 32, 256, 512
    per_layer = d * H * hd + 2 * d * G * hd + H * hd * d + 3 * d * F
    flops = 2 * (L * per_layer + d * V)  # layers and the untied head; no embedding
    # bf16 matrices, f32 norms (attn, mlp, q, k per layer; final), one embedding row
    nbytes = 2 * (L * per_layer + d * V) + 4 * (L * (2 * d + 2 * hd) + d) + 2 * d
    assert costs.step_cost(m) == (flops, nbytes)


def test_tied_head_counts_the_embedding_once():
    m = _models()["minicpm-2b"]
    d, V = 128, 512
    untied = dict(m, tie_word_embeddings=False)
    f_tied, b_tied = costs.step_cost(m)
    f_untied, b_untied = costs.step_cost(untied)
    assert f_tied == f_untied
    assert b_untied - b_tied == 2 * d  # the looked-up row


# the published widths' costs, as step_roofline and mfu have read them since
# the benchmark began
FULL_WIDTH = {
    "qwen3-1.7b": (3_440_902_144, 3_441_401_856),
    "minicpm-2b": (5_449_388_544, 5_450_135_040),
    "mamba2-1.3b": (2_686_451_712, 2_688_098_304),
}


@pytest.mark.parametrize("arch", sorted(FULL_WIDTH))
def test_full_width_costs_pinned(arch):
    for config in ("trio-1.5x", "trio-fit"):
        models = json.loads((ROOT / f"bench/configs/{config}.json").read_text())["models"]
        (m,) = [m for m in models if m["arch"] == arch]
        assert costs.step_cost(m) == FULL_WIDTH[arch]


def test_peaks_known_and_unknown():
    assert costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        costs.peaks("TPU v9 imaginary")
