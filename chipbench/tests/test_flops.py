"""Model FLOPs from shapes, and the peaks table."""

import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def _granite():
    from chipbench.reference import arch_from_config
    conf = json.loads((ROOT / "chipbench/configs/granite-moe-3b-a800m.json")
                      .read_text())
    return arch_from_config(conf["model"])


def test_granite_per_token_by_hand():
    from chipbench.flops import token_cost
    c = token_cost(_granite())
    # one layer, by hand: q 1536x1536, k and v 1536x512 each, o 1536x1536
    #   2*(2359296 + 786432 + 786432 + 2359296)   = 12582912
    # router 1536x40: 2*61440                     =   122880
    # 8 routed experts x 3 matrices of 1536x512:
    #   8*3*2*786432                               = 37748736
    # per layer 50454528; 32 layers
    assert c.fixed == 32 * 50454528 == 1614544896
    # scores and values: 2*2*(24 heads*64) per attended position per layer
    assert c.per_ctx == 32 * 6144
    # unembedding of one row: 2*1536*49155
    assert c.unembed == 151004160
    # a decode token at position 99 attends to 100 positions
    assert c.tokens(1, 100) == 1614544896 + 100 * 196608


def test_peaks_known_and_unknown():
    from chipbench.flops import peak_flops
    assert peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError):
        peak_flops("TPU v9 imaginary")
