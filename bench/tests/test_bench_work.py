"""Operation and byte counts, against counts made by hand for one
layer at qwen3-32b widths (d 5120, d_ff 25600, 64 q / 8 kv heads of
128, vocab 151 936) with half the FFN tiles pruned."""
import json

import numpy as np
import pytest

import work

D, F, H, KH, HD, V = 5120, 25600, 64, 8, 128, 151936
# q 5120x8192 + k, v 5120x1024 each + o 8192x5120
ATTN = 5120 * 8192 + 2 * 5120 * 1024 + 8192 * 5120
# three 5120x25600 matrices in 128x128 tiles, half of them kept
TILES = 3 * (5120 // 128) * (25600 // 128) // 2
FFN = TILES * 128 * 128


def model(layers=1):
    return work.Model(d=D, f=F, heads=H, kv_heads=KH, head_dim=HD,
                      vocab=V, layers=layers, weight_bytes=2,
                      ffn_tiles=(TILES,) * layers)


def test_weights_of_one_layer():
    m = model()
    assert m.attn_weights == ATTN == 94_371_840
    assert m.ffn_weights(0) == FFN == 196_608_000


def test_token_flops_count_weights_attention_and_head():
    m = model()
    attn_ctx = 4 * H * HD * 1000          # q.k and p.v over 1000 keys
    assert m.token_flops(1000, head=False) == 2 * (ATTN + FFN) + attn_ctx
    assert m.token_flops(1000, head=True) == \
        2 * (ATTN + FFN) + attn_ctx + 2 * V * D


def test_prefill_flops_sum_the_causal_context_and_one_head():
    m = model()
    n = 300
    want = sum(m.token_flops(p + 1, head=False) for p in range(n)) \
        + 2 * V * D
    assert m.prefill_flops(n) == pytest.approx(want, rel=1e-12)


def test_ffn_call_and_its_least_time():
    m = model()
    pk = work.peaks("TPU v5 lite")
    dec = m.ffn_call(0, 8)
    assert dec["flops"] == 2 * 8 * FFN
    assert dec["bytes"] == FFN * 2 + 2 * 8 * D * 2
    t = work.least_time(dec["flops"], dec["bytes"], pk)
    assert t["bound"] == "memory"
    assert t["s"] == pytest.approx(dec["bytes"] / 819e9)
    pre = m.ffn_call(0, 8 * 2048)
    t = work.least_time(pre["flops"], pre["bytes"], pk)
    assert t["bound"] == "compute"
    assert t["s"] == pytest.approx(2 * 16384 * FFN / 197e12)


def test_peaks_name_their_source_and_refuse_an_unknown_device():
    with open(work.PEAKS) as fh:
        assert "TPU v5e" in json.load(fh)["source"]
    with pytest.raises(KeyError, match="TPU v9"):
        work.peaks("TPU v9")


def test_nonzero_tiles_are_counted_from_the_weights():
    w = np.ones((2, 256, 384), np.float32)
    w[0, :128, :128] = 0
    w[1, 128:, 256:] = 0
    w[1, 0, 0] = 0                      # a zero inside a kept tile
    assert work.count_nonzero_tiles(w).tolist() == [5, 5]
