"""The traffic generator: seeded, same shapes for every seed, ids inside
the configuration's vocabulary."""
import numpy as np
import pytest

import catalog
from traffic import Traffic, jax_seed

MIXES = ("dense-s1024", "iot-s64", "silo-s1024")


def mix(name):
    return catalog.load_json(catalog.BENCH / "traffic" / f"{name}.json")


def test_same_seed_same_batches_other_seed_other_rows():
    a = Traffic(mix("iot-s64"), 65536, 2**31 + 17).round_batch(3)
    b = Traffic(mix("iot-s64"), 65536, 2**31 + 17).round_batch(3)
    c = Traffic(mix("iot-s64"), 65536, 2**31 + 18).round_batch(3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert a["tokens"].shape == c["tokens"].shape == (2, 8, 4, 64)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_labels_are_next_tokens():
    t = Traffic(mix("dense-s1024"), 65536, 5).client_batch(0, 1)
    np.testing.assert_array_equal(t["tokens"][..., 1:], t["labels"][..., :-1])


def test_sampler_follows_round_and_client_order():
    m = mix("iot-s64")
    t = Traffic(m, 512, 9)
    for r in range(2):
        for c in range(t.clients):
            got = t.sampler(c, t.tau, None)
            np.testing.assert_array_equal(got["tokens"],
                                          t.client_batch(r, c)["tokens"])
    with pytest.raises(ValueError):
        t.sampler(1, t.tau, None)


def test_mistral_share_draws_ids_from_its_vocabulary_slice():
    cfg = catalog.load_json(
        catalog.BENCH / "configs" / "mistral-large-123b-tp8-4l.json")
    assert cfg["vocab_size"] == 4096
    t = Traffic(mix("silo-s1024"), cfg["vocab_size"], 2**33 + 1)
    ids = np.concatenate([t.round_batch(r)["tokens"].ravel()
                          for r in range(4)])
    assert ids.min() >= 0 and ids.max() < 4096
    assert len(np.unique(ids)) > 1000


@pytest.mark.parametrize("name", MIXES)
def test_every_mix_has_the_protocol_keys(name):
    m = mix(name)
    for k in ("engine", "clients", "batch", "seq", "tau", "lr", "clip",
              "sigma", "delta", "epsilon_budget", "trace_rounds", "tokens"):
        assert k in m, k


def test_jax_seed_takes_seeds_past_32_bits():
    s = jax_seed(2**31 + 5)
    assert 0 <= s < 2**31
    assert jax_seed(2**31 + 5) == s != jax_seed(2**31 + 6)
