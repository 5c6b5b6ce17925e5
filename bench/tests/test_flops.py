"""FLOP counts of both configurations against hand counts."""
import json
from pathlib import Path

import pytest

from bench import flops

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


@pytest.mark.parametrize("name,per_layer,layers,embed,attn_per_key", [
    # q 576x576, k and v 576x192, o 576x576, gate/up/down 3 x 576x1536
    ("smollm-135m", 576 * 576 + 2 * 576 * 192 + 576 * 576 + 3 * 576 * 1536, 30,
     49152 * 576, 4 * 9 * 64),
    # q 1024x2048, k and v 1024x1024, o 2048x1024, gate/up/down 3 x 1024x3072
    ("qwen3-0.6b", 1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024 + 3 * 1024 * 3072, 28,
     151936 * 1024, 4 * 16 * 128),
])
def test_token_flops(name, per_layer, layers, embed, attn_per_key):
    m = model(name)
    assert flops.layer_gemm_params(m) == per_layer
    assert flops.token_flops(m, 0, logits=False) == 2 * layers * per_layer
    assert flops.token_flops(m, 100) == 2 * layers * per_layer + 2 * embed + layers * attn_per_key * 100


def test_parameter_counts_match_the_published_sizes():
    # 134.5M and 596M parameters, tied embeddings counted once
    assert 30 * flops.layer_gemm_params(model("smollm-135m")) + 49152 * 576 == 134_479_872
    assert 28 * flops.layer_gemm_params(model("qwen3-0.6b")) + 151936 * 1024 == 595_984_384


@pytest.mark.parametrize("name", ["smollm-135m", "qwen3-0.6b"])
def test_prompt_flops_is_the_sum_over_positions(name):
    m = model(name)
    start, n = 256, 37
    by_position = sum(flops.token_flops(m, start + j + 1, logits=False) for j in range(n))
    unembed = flops.token_flops(m, 0) - flops.token_flops(m, 0, logits=False)
    assert flops.prompt_flops(m, start, n) == pytest.approx(by_position + unembed)
    assert flops.prompt_flops(m, start, n, logits=False) == pytest.approx(by_position)


def test_train_flops_is_three_forwards_at_the_mean_context():
    m = model("smollm-135m")
    assert flops.train_flops_per_token(m, 2048) == pytest.approx(3 * flops.token_flops(m, 1024.5))


def test_masked_matmul_counts():
    f, b = flops.masked_matmul_flops_bytes(5, 1536, 576, 256, 256, x_bytes=2, w_bytes=4, out_bytes=2)
    assert f == 2 * 5 * 1536 * 576 + 1536 * 576
    assert b == 5 * 1536 * 2 + 1536 * 576 * 4 + 5 * 576 * 2 + 256 * 256 * 4
