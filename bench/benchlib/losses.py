"""Next-token cross entropy for the reference models, in blocks of rows."""
from __future__ import annotations

import jax
import jax.numpy as jnp

ROW_BLOCK = 512


def cross_entropy(x, targets, head, ein):
    """Mean over valid targets of logsumexp(x W) - (x W)[target], taken in
    blocks of rows so the (rows, vocab) logits never exist at once."""
    d = x.shape[-1]
    rows = x.reshape(-1, d)
    tgt = targets.reshape(-1)
    rb = min(ROW_BLOCK, rows.shape[0])
    nb = rows.shape[0] // rb

    def block(args):
        xr, tr = args
        logits = ein("rd,dv->rv", xr, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, jnp.maximum(tr, 0)[:, None], axis=-1)[:, 0]
        valid = (tr >= 0).astype(jnp.float32)
        return jnp.sum((lse - picked) * valid), jnp.sum(valid)

    sums, counts = jax.lax.map(
        jax.checkpoint(block), (rows.reshape(nb, rb, d), tgt.reshape(nb, rb)))
    return jnp.sum(sums) / jnp.maximum(jnp.sum(counts), 1.0)
