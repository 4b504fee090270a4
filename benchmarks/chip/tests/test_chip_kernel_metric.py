"""``kernel.mlstm_gla_ms`` reads the mLSTM kernels' device time per
traced step from the trace's operations, in every program, and nothing
where the mixing did not run as the kernels (the parent of the change
that brought them, or a backend without them)."""
import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from chipbench import layers  # noqa: E402


def _ctx(ops, steps=2):
    return types.SimpleNamespace(trace={"ops": ops}, traced_steps=steps)


def test_reads_every_mlstm_gla_call():
    ops = {"mlstm_gla_fwd.3 custom-call": 0.004,
           "mlstm_gla_fwd_res.7 custom-call": 0.006,
           "mlstm_gla_bwd.9 custom-call": 0.012,
           "mlstm_gla_fwd.41 custom-call": 0.008,   # scoring's
           "slstm_fwd.2 custom-call": 0.5, "while.529 while": 0.9}
    got = layers.reader("kernel.mlstm_gla_ms").read(_ctx(ops))
    assert got == pytest.approx(1e3 * 0.030 / 2)


@pytest.mark.parametrize("ops,steps", [
    ({"while.548 while": 0.0136, "slstm_bwd.11 custom-call": 0.0108}, 2),
    ({"mlstm_gla_bwd.9 custom-call": 0.012}, 0)])
def test_nothing_to_read(ops, steps):
    assert layers.reader("kernel.mlstm_gla_ms").read(_ctx(ops, steps)) \
        is None
