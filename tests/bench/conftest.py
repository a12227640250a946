"""Fixtures of the benchmark's CPU tests: the benchmark's own directory
on the import path, and a tiny cell of each architecture."""
import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "bench", ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import catalog  # noqa: E402

# limits of the tiny cells, from CPU readings over ten seeds of each at
# this size: the program read at most loss 3.4e-4, gnorm 2.4e-3, update
# 0.018, change 0.020, drift 0.108; the float8 control at least gnorm
# 0.022, update 1.04, change 0.41, drift 0.955, eps 9.8e-8; half the batch
# gnorm 0.29; no average update 0.41; the largest leaf's gradient reversed
# drift 1.96; every gradient dropped, the noise kept, drift 0.977
TINY_LIMITS = {"loss_gap": 2e-3, "gnorm_gap": 1e-2, "update_gap": 0.1,
               "change_gap": 0.1, "drift_gap": 0.4, "eps_gap": 1e-9}


def tiny_cell(family: str = "rwkv6"):
    """One layer at width 64, vocab 256, 2 clients x batch 2 x 16 tokens x
    tau 2: the cell's whole path, at a size a CPU test holds."""
    if family == "rwkv6":
        config = catalog.load_json(
            catalog.BENCH / "configs" / "rwkv6-1.6b-3l.json")
        config.update(hidden_size=64, intermediate_size=128, vocab_size=256,
                      head_size=16, num_hidden_layers=1, decay_lora_rank=8)
        config["program"] = {"arch": "rwkv6-1.6b", "n_layers": 1,
                             "tie_head": False, "d_model": 64, "d_ff": 128,
                             "vocab": 256, "rwkv_headdim": 16, "n_heads": 4,
                             "n_kv_heads": 4, "lora_rank": 8}
    else:
        config = catalog.load_json(
            catalog.BENCH / "configs" / "mistral-large-123b-tp8-4l.json")
        config.update(hidden_size=64, intermediate_size=1024, vocab_size=256,
                      head_dim=16, num_hidden_layers=1,
                      num_attention_heads=4, num_key_value_heads=2)
        config["program"] = {"arch": "mistral-large-123b", "n_layers": 1,
                             "tie_head": False, "d_model": 64, "d_ff": 128,
                             "vocab": 256, "head_dim": 16, "n_heads": 4,
                             "n_kv_heads": 2}
    mix = catalog.load_json(catalog.BENCH / "traffic" / "iot-s64.json")
    # weights of width 64 are about 8x the size of the cells' (1/sqrt(d)):
    # a larger noise keeps each step's move at a few bfloat16 steps there
    mix.update(clients=2, batch=2, seq=16, tau=2, trace_rounds=2, sigma=0.2)
    e2e = tuple(m for m in catalog.benchmark()["end_to_end"])
    return catalog.Cell(f"tiny-{family}", 1, config, mix,
                        {"limits": dict(TINY_LIMITS), "loss_chunk": 16},
                        e2e, ())


@pytest.fixture
def tiny():
    return copy.deepcopy(tiny_cell())
