"""The operation and byte counts against hand counts at small shapes, and
the peak table's refusal of an unknown device."""
import pytest

from bench.harness import flops as F
from bench.harness import peaks
from bench.harness.model import Shard


def shard(**kw):
    base = dict(n_layers=2, d_model=8, n_heads=4, n_kv_heads=2, head_dim=2,
                d_ff=16, vocab=10, norm_eps=1e-5, rope_theta=1e4,
                dtype="float32")
    base.update(kw)
    return Shard(**base)


def test_matmul_params_by_hand():
    # per layer: wq 8x8, wk 8x4, wv 8x4, wo 8x8, w1/w3 8x16, w2 16x8
    per_layer = 64 + 32 + 32 + 64 + 128 + 128 + 128
    assert F.matmul_params(shard()) == 2 * per_layer + 8 * 10


def test_train_flops_per_token_by_hand():
    s = shard()
    # seq 3: positions attend to 1, 2, 3 keys -> mean 2; per key and
    # layer 4 * heads * head_dim = 32 forward operations
    attn = 2 * 32 * 2.0
    want = 3 * (2 * F.matmul_params(s) + attn)
    assert F.train_flops_per_token(s, 3) == pytest.approx(want)


def test_gossip_mix_cost_by_hand():
    # 2 learners x 8 rows x 128 lanes, one neighbour: read w, nbr, g, mu,
    # write w, mu = 6 float32 streams; 2*2 + 4 operations per element
    flops, bytes_ = F.gossip_mix_cost(2, 8, 1)
    elems = 2 * 8 * 128
    assert bytes_ == 6 * 4 * elems
    assert flops == 8 * elems


def test_peaks_table_has_a_source_and_refuses_unknown_devices():
    p = peaks.peak("TPU v5 lite")
    assert p["flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")
