"""Serving: prefill and decode step builders (per-replica, no gossip).

Serving is a single-replica workload: weights are sharded over the ``model``
axis only (replicated across data/pod axes); the request batch is sharded
over the non-model axes.  Decode states get explicit per-family shardings:

  kv cache   (L, B, slots, KV, Dh): batch over data axes; KV heads over
             ``model`` when divisible, else slots over ``model``.
  rwkv state (L, B, H, N, N): heads over ``model``.
  mamba      (..., B, H, P, N): heads over ``model``; conv tail d_inner over
             ``model``.

``long_500k`` (B = 1) cannot shard the batch: the cache slot dim takes the
combined (data, model) axes instead and full-attention archs run their
sliding-window ring cache (``cfg.sliding_window``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, InputShape
from repro.launch import sharding as shd
from repro.models import transformer as tfm
from repro.models.common import abstract_params, spec_tree

PyTree = Any

__all__ = ["ServeEngine", "DEFAULT_WINDOW"]

DEFAULT_WINDOW = 8192  # sliding window for full-attention archs on long_500k


def _divides(n: int, size: int) -> bool:
    return size > 0 and n % size == 0


class ServeEngine:
    """Builds sharded prefill/decode steps for one (arch × mesh)."""

    def __init__(self, cfg: ArchConfig, mesh: jax.sharding.Mesh):
        self.cfg = cfg
        self.mesh = mesh
        self.data_axes = tuple(a for a in mesh.axis_names if a != "model")
        self.tp = mesh.shape.get("model", 1)
        self.defs = tfm.model_defs(cfg, tp_size=self.tp)
        self.param_shardings = shd.param_shardings(
            abstract_params(self.defs),
            spec_tree(self.defs),
            mesh,
            (),
            stacked=False,
            fsdp=False,
        )

    # -- sharding helpers -------------------------------------------------------
    def _batch_axes(self, b: int):
        size = int(np.prod([self.mesh.shape[a] for a in self.data_axes])) if self.data_axes else 1
        return self.data_axes if _divides(b, size) else None

    def _state_shardings(self, state_abs: PyTree, b: int) -> PyTree:
        batch_ax = self._batch_axes(b)
        model = "model"
        msize = self.tp
        combined = (
            tuple(self.data_axes) + ("model",) if batch_ax is None else None
        )
        csize = int(np.prod([self.mesh.shape[a] for a in self.mesh.axis_names]))

        def rule(leaf):
            shape = leaf.shape
            nd = len(shape)
            spec = [None] * nd
            # find the batch dim: the first dim equal to b after leading stack dims
            bdim = next(
                (i for i, s in enumerate(shape) if s == b and i <= 2), None
            )
            if bdim is not None and batch_ax is not None:
                spec[bdim] = batch_ax
            # shard one more dim over `model` (prefer head-like dims right of batch)
            start = (bdim + 1) if bdim is not None else 0
            cands = [i for i in range(start, nd) if spec[i] is None]
            # prefer later, smaller "head" dims over the huge slot dim when both work
            for i in sorted(cands, key=lambda i: (shape[i] > 1024, -i)):
                if _divides(shape[i], msize):
                    spec[i] = model
                    break
            # long-context B=1: put the combined axes on the big slot dim
            if batch_ax is None and combined:
                for i in cands:
                    if spec[i] is None and shape[i] >= csize and _divides(shape[i], csize):
                        if model in spec:
                            spec[spec.index(model)] = None
                        spec[i] = combined
                        break
            return NamedSharding(self.mesh, P(*spec))

        return jax.tree.map(rule, state_abs)

    # -- prefill -----------------------------------------------------------------
    def prefill_fn(self):
        # reference attention materializes (B, H, S, S) — never at 32k.
        # an explicit chunked-family override (e.g. chunked_skip) is honored.
        cfg = (
            self.cfg
            if self.cfg.attn_impl.startswith("chunked")
            else dataclasses.replace(self.cfg, attn_impl="chunked")
        )

        def fn(params, tokens, patch_embeds=None):
            return tfm.prefill(params, cfg, tokens, patch_embeds=patch_embeds)

        return fn

    def lower_prefill(self, shape: InputShape):
        from repro.configs.base import input_specs

        batch = input_specs(self.cfg, shape)
        b = shape.global_batch
        batch_ax = self._batch_axes(b)
        bspec = lambda nd: NamedSharding(self.mesh, P(batch_ax, *([None] * (nd - 1))))
        in_sh = jax.tree.map(lambda l: bspec(len(l.shape)), batch)
        fn = self.prefill_fn()
        args = (batch["tokens"],)
        in_shardings = (self.param_shardings, in_sh["tokens"])
        if "patch_embeds" in batch:
            args += (batch["patch_embeds"],)
            in_shardings += (in_sh["patch_embeds"],)
        with jax.set_mesh(self.mesh):
            return jax.jit(fn, in_shardings=in_shardings).lower(
                abstract_params(self.defs), *args
            )

    # -- decode ---------------------------------------------------------------------
    def decode_window(self, shape: InputShape) -> Optional[int]:
        """Sliding window if this arch needs one at this context length."""
        if self.cfg.family in ("ssm",):
            return None
        if shape.seq_len > 100_000:
            return self.cfg.sliding_window or DEFAULT_WINDOW
        return None

    def decode_fn(self, window: Optional[int]):
        cfg = self.cfg

        def fn(params, tokens, pos, state):
            return tfm.decode_step(params, cfg, tokens, pos, state, window=window)

        return fn

    def abstract_decode_state(self, shape: InputShape):
        window = self.decode_window(shape)
        return (
            jax.eval_shape(
                lambda: tfm.init_decode_state(
                    self.cfg, shape.global_batch, shape.seq_len, window=window,
                    tp_size=self.tp,
                )
            ),
            window,
        )

    def lower_decode(self, shape: InputShape):
        from repro.configs.base import input_specs

        state_abs, window = self.abstract_decode_state(shape)
        state_sh = self._state_shardings(state_abs, shape.global_batch)
        batch = input_specs(self.cfg, shape)
        batch_ax = self._batch_axes(shape.global_batch)
        tok_sh = NamedSharding(self.mesh, P(batch_ax, None))
        pos_sh = NamedSharding(self.mesh, P())
        fn = self.decode_fn(window)
        with jax.set_mesh(self.mesh):
            return jax.jit(
                fn,
                in_shardings=(self.param_shardings, tok_sh, pos_sh, state_sh),
                donate_argnums=(3,),
            ).lower(
                abstract_params(self.defs), batch["tokens"], batch["pos"], state_abs
            )

    # -- concrete serving loop (CPU-scale demo) ---------------------------------------
    def generate(
        self,
        params,
        prompts: jax.Array,
        n_new: int,
        *,
        patch_embeds=None,
        max_len: Optional[int] = None,
        temperature: float = 0.0,
        key: Optional[jax.Array] = None,
    ):
        """Batched greedy/sampled generation (runs on any mesh incl. CPU)."""
        cfg = self.cfg
        b, s0 = prompts.shape
        n_patches = cfg.n_patches if (cfg.input_kind == "vlm" and patch_embeds is not None) else 0
        max_len = max_len or (s0 + n_patches + n_new)
        logits, _ = tfm.prefill(params, cfg, prompts, patch_embeds=patch_embeds)
        # re-run prefill into a right-sized cache by decoding from scratch is
        # wasteful; instead allocate the full cache and replay the prompt.
        state = tfm.init_decode_state(cfg, b, max_len)
        pos = jnp.int32(0)
        last = None
        step = jax.jit(
            lambda p, t, ps, st: tfm.decode_step(p, cfg, t, ps, st)
        )
        if n_patches:
            # feed patch positions as a pseudo-prompt is out of scope for the
            # demo loop: VLM generation starts after text-only replay.
            pass
        for t in range(s0):
            last, state = step(params, prompts[:, t : t + 1], pos, state)
            pos = pos + 1
        out = []
        tok = None
        for i in range(n_new):
            if temperature > 0.0 and key is not None:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(sub, last / temperature)[:, None]
            else:
                tok = jnp.argmax(last, axis=-1)[:, None]
            out.append(tok)
            last, state = step(params, tok, pos, state)
            pos = pos + 1
        return jnp.concatenate(out, axis=1)
