"""Plain forward pass of the GreenFlow reward model.

Written from the published description (arXiv:2312.16176 4.2), as the
configuration sizes it: a context encoder, three recursive stage cells
and multi-basis monotone heads, per chain; float32 activations, every
matrix product accumulated in float32 at full precision from operands
rounded as the configuration states: to bfloat16 on a TPU, whose
default precision computes so, and not at all on a backend whose
default is float32.  The ``control`` precision rounds every matmul
operand to float8 (e4m3) instead, one step below, and is what a cheaper
program would compute.  Weights are read by name from the tree
``bench.weights`` made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class Precision:
    """Operand rounding of every matmul: the stated one, or the
    control's."""

    def __init__(self, control: bool = False):
        self.control = control
        if control:
            self.operand = jnp.float8_e4m3fn
        elif jax.default_backend() == "tpu":
            self.operand = jnp.bfloat16
        else:
            self.operand = None

    def q(self, x):
        if self.operand is None:
            return x
        return x.astype(self.operand).astype(jnp.float32)

    def mm(self, eq: str, a, b):
        return jnp.einsum(eq, self.q(a), self.q(b), precision=HIGHEST)

    def dense(self, layer, x):
        return self.mm("...i,io->...o", x, layer["w"]) + layer["b"]


def _mlp(pr, layers, x, act, final=None):
    for i, layer in enumerate(layers):
        x = pr.dense(layer, x)
        if i < len(layers) - 1:
            x = act(x)
        elif final is not None:
            x = final(x)
    return x


_BASES = (jnp.tanh, jnp.log1p, lambda v: v / jnp.sqrt(1.0 + v * v),
          jax.nn.sigmoid, lambda v: v)


def reward(pr, params, ctx, onehot, multihot):
    """(n, J) predicted reward of every chain for every request."""
    f = _mlp(pr, params["encoder"]["layers"], ctx, jax.nn.relu)
    n, j_n = ctx.shape[0], onehot.shape[0]
    cells = params["cells"]
    h = jnp.zeros((n, j_n, cells[0]["state"]["w"].shape[1]))
    total = jnp.zeros((n, j_n))
    fb = jnp.broadcast_to(f[:, None], (n, j_n, f.shape[-1]))
    for k, cell in enumerate(cells):
        # a one-hot row selection: exact, no product to round
        m_emb = jnp.einsum("jm,me->je", onehot[:, k], cell["model_emb"],
                           precision=HIGHEST)
        z = jnp.concatenate([h, fb, jnp.broadcast_to(
            m_emb[None], (n,) + m_emb.shape)], axis=-1)
        t = _mlp(pr, cell["trunk"]["layers"], z, jax.nn.relu, jax.nn.relu)
        w = jax.nn.softmax(pr.dense(cell["w_head"], t), axis=-1)
        u = jax.nn.softplus(pr.dense(cell["v_heads"], t))
        u = u.reshape(n, j_n, len(_BASES), -1)
        v = pr.mm("njpq,jq->njp", u, multihot[:, k])
        phi = jnp.stack([b(v[..., i]) for i, b in enumerate(_BASES)], -1)
        total = total + jnp.sum(w * phi, axis=-1)
        h = jnp.tanh(pr.dense(cell["state"], t))
    return total * params["label_norm"][None, :]
