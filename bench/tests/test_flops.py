"""FLOPs per token of both configurations against a count by hand."""

import json

import flops
from conftest import BENCH


def model(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())[
        "model"]


def test_mamba2_flops_per_token():
    # per layer: in_proj 2*2048*(2*4096 + 2*128 + 64) = 34,865,152
    #            out_proj 2*4096*2048                = 16,777,216
    #            SSD at chunk 256: 2*256*128 + 2*256*4096
    #                              + 2 * 2*4096*128  = 4,259,840
    # 16 layers = 894,435,328; head 2*2048*50432 = 206,569,472
    fwd = 16 * (34_865_152 + 16_777_216 + 4_259_840) + 206_569_472
    assert flops.per_token(model("mamba2-1.3b"), 2048) == 3 * fwd
    assert abs(3 * fwd / 1e9 - 3.3030) < 1e-3


def test_tinyllama_flops_per_token():
    # per layer: Q+O 2*2*2048*2048 = 16,777,216; K+V 2*2*2048*256 = 2,097,152
    #            MLP 6*2048*5632                  = 69,206,016
    #            causal attention 4*32*64*(2049/2) = 8,392,704
    # 7 layers = 677,278,976; head 2*2048*32000 = 131,072,000
    fwd = 7 * (16_777_216 + 2_097_152 + 69_206_016 + 8_392_704) + 131_072_000
    assert flops.per_token(model("tinyllama-1.1b"), 2048) == 3 * fwd
