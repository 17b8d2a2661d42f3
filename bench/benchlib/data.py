"""Training rows from a seed: the synthetic language-model stream.

A copy of the program's ``repro.data.SyntheticLM`` generator (same
algorithm, same numbers for the same seed), kept here so that the data the
benchmark feeds and the data the reference sees come from the yardstick and
not from the code under test.  Each (seed, node, step) has its own stream,
so every node's rows differ at every step.

Traffic file keys: ``seq`` (tokens per row), ``per_node_batch`` (rows per
node per step), ``structure`` (share of tokens that follow the stream's
deterministic rule; the rest are uniform).
"""
from __future__ import annotations

import numpy as np


def rows(vocab: int, seq: int, batch: int, *, seed: int, node: int, step: int,
         structure: float) -> dict[str, np.ndarray]:
    """(tokens, targets), each (batch, seq) int32; targets[t] = tokens[t+1],
    and the last position is masked with -1."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, node, step]))
    toks = np.empty((batch, seq + 1), np.int32)
    toks[:, 0] = rng.integers(0, vocab, batch)
    mult = 6364136223846793005 % vocab
    for t in range(seq):
        follow = rng.random(batch) < structure
        nxt = (toks[:, t] * mult + 12345) % vocab
        rand = rng.integers(0, vocab, batch)
        toks[:, t + 1] = np.where(follow, nxt, rand)
    targets = toks[:, 1:].copy()
    targets[:, -1] = -1
    return {"tokens": toks[:, :-1], "targets": targets}


def stacked(traffic: dict, vocab: int, nodes: int, step: int, seed: int) -> dict:
    """Every node's rows for one step, stacked (nodes, batch, seq)."""
    outs = [
        rows(vocab, traffic["seq"], traffic["per_node_batch"], seed=seed,
             node=i, step=step, structure=traffic["structure"])
        for i in range(nodes)
    ]
    return {k: np.stack([o[k] for o in outs]) for k in outs[0]}
