"""Work counted from shapes against hand counts, and the peaks table."""
import pytest

import catalog
import flops

RWKV = catalog.load_json(catalog.BENCH / "configs" / "rwkv6-1.6b-3l.json")
MISTRAL = catalog.load_json(
    catalog.BENCH / "configs" / "mistral-large-123b-tp8-4l.json")


def test_rwkv_share_parameters():
    # per layer: time mix 5d + 5d^2 + d (w0) + 2 d r (decay LoRA) + d (u)
    # + d (ln), channel mix 2d + 2 d f + d^2, two norms 2d; a 65536 x d
    # embedding and an untied head of the same size; final norm d
    d, f, r = 2048, 7168, 64
    layer = (5 * d + 5 * d * d + d + 2 * d * r + d + d
             + 2 * d + 2 * d * f + d * d + 2 * d)
    assert 3 * layer + 2 * 65536 * d + d == 432_875_520
    assert flops.params(RWKV) == 432_875_520
    assert RWKV["tie_word_embeddings"] is False


@pytest.mark.parametrize("tied, want", [(True, 742_502_400),
                                        (False, 792_834_048)])
def test_mistral_share_parameters(tied, want):
    # per layer: q 12288 x 12 x 128, k and v 12288 x 1 x 128 each, o back,
    # SwiGLU 3 x 12288 x 3584, two norms; a 4096-row embedding (and head)
    cfg = dict(MISTRAL, tie_word_embeddings=tied)
    assert flops.params(cfg) == want
    assert MISTRAL["tie_word_embeddings"] is False


def test_dp_bytes_count_eight_per_element():
    assert flops.DP_BYTES_PER_ELEMENT == 8
    assert flops.dp_bytes(RWKV) == 8 * 432_875_520


def test_round_flops_rwkv_dense():
    mix = catalog.load_json(catalog.BENCH / "traffic" / "dense-s1024.json")
    d, f, r, hd, v = 2048, 7168, 64, 64, 65536
    per_token = 3 * (6 * (6 * d * d + 2 * d * r + 2 * d * f) + 12 * hd * d) \
        + 6 * v * d
    assert flops.round_flops(RWKV, mix) == per_token * 2 * 2 * 1 * 1024


def test_round_flops_mistral_counts_causal_attention():
    mix = catalog.load_json(catalog.BENCH / "traffic" / "silo-s1024.json")
    d, h, kv, hd, f, v, s = 12288, 12, 1, 128, 3584, 4096, 1024
    matmul = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    per_token = 4 * (6 * matmul + 3 * 2 * s * hd * h) + 6 * v * d
    assert flops.round_flops(MISTRAL, mix) == per_token * 1024 * 2


def test_peaks_known_kind_and_unknown_is_an_error():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert p["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
