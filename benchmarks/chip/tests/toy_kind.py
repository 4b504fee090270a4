"""A block kind for the tests alone, never a benchmark kind: it holds
weights both once per segment and once per repeat, and reads the input
embeddings,

    x -> x + tanh(concat(x, x0) S) W_i

with S (2d, d) shared by every invocation in its segment, W_i (d, d) the
repeat's own, and x0 the embedding lookup. A test puts this module in
``sys.modules`` under ``MODULE``, where ``reference.block_module(KIND)``
finds it.
"""
import jax.numpy as jnp

from chipbench.refs.common import dense

KIND = "toy_hybrid"
USES_X0 = True
MODULE = f"chipbench.refs.{KIND}"


def spec(cfg):
    d = cfg["d_model"]
    return {"w": ((d, d), "model", dense(d))}


def shared_spec(cfg):
    d = cfg["d_model"]
    return {"s": ((2 * d, d), "model", dense(2 * d))}


def apply(p, x, cfg, num, *, shared, x0):
    h = jnp.tanh(num.mm(jnp.concatenate([x, x0], axis=-1), shared["s"]))
    return x + num.mm(h, p["w"])


def forward_flops(cfg, T):
    d = cfg["d_model"]
    return float(2 * T * (2 * d * d + d * d))

