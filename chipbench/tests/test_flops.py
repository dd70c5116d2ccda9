"""Model FLOPs per token of both configurations, as the reference module
each names counts them (``reference/decoder.py``), against a count by hand:
the numbers the count gave when it was ``chipbench/flops.py``."""

import json

from chipbench import BENCH, reference
from chipbench.reference.decoder import active_params


def _cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def train_flops_per_token(cfg_file, seq_len):
    return reference.load(cfg_file).train_flops_per_token(cfg_file, seq_len)


def test_qwen2_8_layers():
    # a layer: q,o 1536x1536 each, k,v 1536x256 each, biases 1536+256+256,
    # MLP 3 x 1536x8960, two norms of 1536
    layer = 2 * 2359296 + 2 * 393216 + 2048 + 41287680 + 3072
    assert layer == 46797824
    params = 8 * layer + 1536 + 233373696          # final norm, tied head
    assert active_params(_cfg("qwen2-1.5b")) == params == 607757824
    # causal half of 2048 positions, 12 heads of 128, q.k and p.v, x3, 8 layers
    attention = 3 * 8 * 2 * 12 * 1024 * 256
    assert train_flops_per_token(_cfg("qwen2-1.5b"), 2048) == 6 * params + attention
    assert 6 * params + attention == 3797541888


def test_granite_moe_6_layers():
    # a layer: q,o 1024x1024, k,v 1024x512, router 1024x32, 8 of 32 experts
    # of 3 x 1024x512, two norms of 1024
    layer = 2 * 1048576 + 2 * 524288 + 32768 + 8 * 1572864 + 2048
    assert layer == 15763456
    params = 6 * layer + 1024 + 50334720           # final norm, tied head
    assert active_params(_cfg("granite-moe-1b-a400m")) == params == 144916480
    attention = 3 * 6 * 2 * 16 * 2048 * 128
    assert train_flops_per_token(_cfg("granite-moe-1b-a400m"), 4096) == 6 * params + attention
    assert 6 * params + attention == 1020493824
