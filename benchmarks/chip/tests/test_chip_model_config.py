"""``harness.model_config``: the program's ``ModelConfig`` from a
configuration file's dict, each nested dataclass field built by its type
and each segment with every field the file gives."""
import dataclasses
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from chipbench import harness  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,  # noqa: E402
                                Segment, SSMConfig)


def test_nested_dataclasses_and_dense_ffn():
    moe = {"n_experts": 8, "top_k": 2, "d_expert": 96, "n_shared_experts": 1,
           "capacity_factor": 1.5, "shard_mode": "ep"}
    mla = {"q_lora_rank": 48, "kv_lora_rank": 32, "rope_head_dim": 16,
           "nope_head_dim": 32, "v_head_dim": 32}
    ssm = {"d_state": 16, "d_conv": 4, "expand": 2, "head_dim": 16,
           "chunk": 32}
    cfg = {"name": "mixed", "family": "moe", "d_model": 64, "n_heads": 4,
           "n_kv_heads": 2, "d_ff": 128, "vocab_size": 256,
           "segments": [{"pattern": ["mla"], "repeats": 1, "dense_ffn": True},
                        {"pattern": ["mla", "mamba2"], "repeats": 3}],
           "moe": moe, "mla": mla, "ssm": ssm, "tie_embeddings": True,
           "dtype": "float32", "source": "not a field", "assumed": ["nor"]}
    want = ModelConfig(
        name="mixed", family="moe", d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab_size=256,
        segments=(Segment(("mla",), 1, dense_ffn=True),
                  Segment(("mla", "mamba2"), 3)),
        moe=MoEConfig(**moe), mla=MLAConfig(**mla), ssm=SSMConfig(**ssm),
        tie_embeddings=True, dtype="float32")
    got = harness.model_config(cfg)
    assert got == want
    assert isinstance(got.moe, MoEConfig) and isinstance(got.mla, MLAConfig)
    assert got.segments[0].dense_ffn and not got.segments[1].dense_ffn


def test_nested_field_left_out_keeps_its_default():
    cfg = json.loads((HERE.parent / "configs" / "xlstm-350m.json").read_text())
    del cfg["ssm"]
    assert harness.model_config(cfg).ssm == SSMConfig()


@pytest.mark.parametrize("name", ["xlstm-350m", "zamba2-1.2b"])
def test_committed_configs_build_the_registered_model(name):
    """Every field the program's registered model has, the segments as the
    file cuts them (pattern and repeats; no ``dense_ffn``)."""
    cfg = json.loads((HERE.parent / "configs" / f"{name}.json").read_text())
    want = dataclasses.replace(get_config(name), segments=tuple(
        Segment(tuple(s["pattern"]), s["repeats"]) for s in cfg["segments"]))
    assert harness.model_config(cfg) == want
