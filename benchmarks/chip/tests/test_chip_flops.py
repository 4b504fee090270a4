"""The model-FLOP count behind ``mfu`` against hand counts, and the peaks
table.

Hand counts at d_model 8, 2 heads, d_ff 12, vocabulary 10, Mamba2 state
4 / head size 4 / expansion 2, chunk 4, 8 tokens (2·m·k·n per product):

mLSTM (inner 16, head size 8): W_up 2·8·8·32 = 4096, W_q/W_k/W_v
3·2·8·16·16 = 12288, gates 2·8·16·4 = 1024, W_down 2·8·16·8 = 2048;
recurrence: 8·5/2 = 20 causal pairs in chunks of 4, each 2·(8+9) per
head, plus 4·8·9 per token and head for the state: 2·(20·34 + 8·288)
= 5968. Total 25424.

sLSTM (head size 4): gates 2·8·8·32 = 4096, recurrent 2·8·2·4·16 = 2048,
W_out 2·8·8·8 = 1024. Total 7168.

Mamba2 (inner 16, 4 heads): W_zx 2·8·8·32 = 4096, W_bcdt 2·8·8·12 =
1536, W_out 2·8·16·8 = 2048; 20 pairs · 2·4 for C·B = 160, and per head
20·2·4 + 8·4·4·4 = 672, ·4 heads = 2688. Total 10528.

Shared attention (head size 4): Q/K/V 2·8·8·24 = 3072, W_o 1024, SwiGLU
3·2·8·8·12 = 4608, attention 36 causal pairs · 2·2·4 · 2 heads = 1152.
Total 9856. Logits 2·8·8·10 = 1280.
"""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import flops, layers, reference  # noqa: E402

CFG = {"d_model": 8, "n_heads": 2, "n_kv_heads": 2, "head_dim": 0,
       "d_ff": 12, "vocab_size": 10,
       "ssm": {"d_state": 4, "d_conv": 4, "expand": 2, "head_dim": 4,
               "chunk": 4}}
T = 8
HAND = {"mlstm": 25424, "slstm": 7168, "mamba2": 10528,
        "shared_attn": 9856}


@pytest.mark.parametrize("kind", sorted(HAND))
def test_block_forward_flops(kind):
    assert reference.block_module(kind).forward_flops(CFG, T) == HAND[kind]


def test_row_and_step_flops():
    cfg = dict(CFG, segments=[{"pattern": ["mlstm", "slstm"],
                               "repeats": 3}])
    row = 3 * (HAND["mlstm"] + HAND["slstm"]) + 1280
    assert reference.flops_per_row(cfg, T) == row
    tr = {"global_batch": 4, "seq_len": T, "presample_ratio": 3,
          "scheme": "presample"}
    assert flops.step_flops(cfg, tr) == (3 * 4 + 12) * row
    assert flops.step_flops(cfg, dict(tr, scheme="uniform")) == 12 * row


def test_peaks_table():
    assert flops.peak("TPU v5 lite") == 197e12
    assert flops.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        flops.peak("cpu")


@pytest.mark.parametrize("chips", [1, 4])
def test_mfu_is_per_chip(chips):
    """The same traced steps over a cell of ``chips`` chips read 1/chips
    of the one-chip share: the peak is the cell's chips' together."""
    import types
    cfg = dict(CFG, segments=[{"pattern": ["mlstm", "slstm"],
                               "repeats": 3}])
    tr = {"global_batch": 4, "seq_len": T, "presample_ratio": 3,
          "scheme": "presample"}
    ctx = types.SimpleNamespace(traced_steps=2, trace={"window_s": 1e-6},
                                cfg=cfg, traffic=tr,
                                device_kind="TPU v5 lite", chips=chips)
    want = 100.0 * 2 * flops.step_flops(cfg, tr) / 1e-6 / 197e12
    assert layers.reader("mfu").read(ctx) == pytest.approx(want / chips,
                                                           rel=1e-12)
