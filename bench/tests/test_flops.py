"""The FLOP and byte functions against counts made by hand: the shared
counting of ``bench/flops.py`` and the decoder's model FLOPs."""

import pytest

from bench import flops
from bench.arch import decoder

#: one layer, d 4, ffn 8, 2 query heads and 1 KV head of width 2, 10 words
CFG = dict(hidden_size=4, intermediate_size=8, num_hidden_layers=1,
           num_attention_heads=2, num_key_value_heads=1, head_dim=2,
           vocab_size=10, sliding_window=None)


def test_matmul_params():
    # wq 4x4, wk 4x2, wv 4x2, wo 4x4, gate/up 4x8, down 8x4; head 4x10
    assert decoder.matmul_params(CFG) == (16 + 8 + 8 + 16 + 96, 40)


def test_attended_pairs_causal_and_windowed():
    assert flops.attended_pairs(CFG, 3) == 1 + 2 + 3
    assert flops.attended_pairs(dict(CFG, sliding_window=2), 3) == 1 + 2 + 2


def test_train_prefill_decode_flops():
    attn = 4 * 2 * 2 * 6            # QK^T and PV: 4 * heads * width * pairs
    assert decoder.train_flops(CFG, 1, 3) == 3 * (2 * 184 * 3 + attn)
    # prefill unembeds each row's last position only
    assert decoder.prefill_flops(CFG, 1, 3) == 2 * 144 * 3 + 2 * 40 + attn
    # two rows, each attending to three cached positions
    assert decoder.decode_flops(CFG, 2, 3) == 2 * 184 * 2 + 4 * 2 * 2 * 6


def test_kernel_costs():
    # flash, batch 1, 3 positions: q and o are 1x3x2x2, k and v 1x3x1x2
    assert flops.flash_cost(CFG, 1, 3) == (96, (24 + 12) * 2)
    # decode, 2 rows at 3 positions: q and o 2x2x2, k and v 2x3x1x2
    assert flops.decode_attn_cost(CFG, 2, 3) == (96, (16 + 24) * 2)


@pytest.mark.parametrize("work,bound", [((96, 72), "memory"),
                                        ((2000, 72), "compute")])
def test_least_seconds_names_its_bound(work, bound):
    peaks = dict(bf16_flops=100.0, hbm_bytes=10.0)
    t, which = flops.least_seconds(*work, peaks)
    assert which == bound
    assert t == max(work[0] / 100.0, work[1] / 10.0)
