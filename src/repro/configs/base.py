"""Architecture & input-shape configuration schema.

Every assigned architecture is an ``ArchConfig`` (one module per arch under
``repro/configs``); every benchmark input is an ``InputShape``.  The dry-run
crosses them.  ``reduced()`` yields the CPU smoke-test variant of the same
family (≤2 layers, d_model ≤ 512, ≤4 experts).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

__all__ = ["ArchConfig", "InputShape", "SHAPES", "input_specs"]


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    d_ff: int
    vocab: int
    n_heads: int = 0             # 0 for attention-free
    n_kv: int = 0
    d_head: int = 0              # 0 => d_model // n_heads
    qkv_bias: bool = False
    norm: str = "rmsnorm"        # rmsnorm | layernorm
    rope_theta: float = 10_000.0
    act: str = "silu"
    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    # SSM / hybrid
    ssm_state: int = 0
    attn_every: int = 0          # hybrid: shared attn block every N-th block
    # modality stubs
    input_kind: str = "tokens"   # tokens | vlm
    n_patches: int = 0
    # impl knobs
    attn_impl: str = "auto"  # auto (splash on TPU where exact, else reference)
    #   | reference | chunked | chunked_skip  (models/attention.resolve_impl)
    attn_chunk: int = 1024
    pad_heads: bool = False  # pad GQA groups so heads shard on the model axis
    #   (exact: padded heads are masked; see models/attention.head_padding)
    pad_kv: bool = False     # also pad kv heads to the model axis (shards KV caches)
    sliding_window: Optional[int] = None  # serving window for long_500k
    rec_chunk: int = 64          # recurrence chunk (ssm/hybrid)
    scan_layers: bool = True
    remat: bool = True
    remat_policy: str = "full"   # full | dots (jax.checkpoint_policies.dots_saveable)
    moe_shard_ff: bool = False   # shard expert d_ff over the data axis (2-level
    #   TP) instead of FSDP weight-gathering — kills per-layer expert gathers
    moe_buf_constraint: bool = False  # with_sharding_constraint the (E, C, D)
    #   dispatch buffer to P("model") — only valid on plain-jit (G=1) paths
    moe_impl: str = "gather"  # gather (GSPMD auto) | manual_ep (explicit
    #   shard_map EP: one psum/layer — §Perf H2/H4 follow-up; needs jax.set_mesh)
    dtype: Any = jnp.bfloat16
    # citation for the config numbers
    source: str = ""

    @property
    def head_dim(self) -> int:
        if self.d_head:
            return self.d_head
        return self.d_model // max(self.n_heads, 1)

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    def reduced(self) -> "ArchConfig":
        """CPU smoke-test variant of the same family."""
        d_model = min(self.d_model, 256)
        n_heads = min(self.n_heads, 4) if self.n_heads else 0
        n_kv = min(self.n_kv, max(n_heads // 2, 1)) if self.n_kv else 0
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            n_layers=min(self.n_layers, 2) if not self.attn_every
            else min(self.n_layers, self.attn_every + 1),
            d_model=d_model,
            d_ff=min(self.d_ff, 512),
            vocab=min(self.vocab, 512),
            n_heads=n_heads,
            n_kv=n_kv,
            d_head=(d_model // n_heads if n_heads else 0),
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            n_shared_experts=min(self.n_shared_experts, 1),
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            n_patches=min(self.n_patches, 8) if self.n_patches else 0,
            rec_chunk=8,
            attn_chunk=64,
            dtype=jnp.float32,
        )


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES: dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


def input_specs(
    cfg: ArchConfig, shape: InputShape, *, n_nodes: int = 1
) -> dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for the step function's data inputs.

    Training batches carry the gossip-node axis (G, per_node_batch, S);
    serving batches are flat (B, ...).  Modality frontends are stubbed per
    the harness spec: VLM patch embeddings arrive precomputed.
    """
    i32 = jnp.int32
    if shape.kind == "train":
        if shape.global_batch % n_nodes:
            raise ValueError(
                f"{shape.name}: global_batch {shape.global_batch} not divisible "
                f"by {n_nodes} gossip nodes"
            )
        b = shape.global_batch // n_nodes
        s = shape.seq_len
        lead = (n_nodes, b)
        specs = {
            "tokens": jax.ShapeDtypeStruct(lead + (s,), i32),
            "targets": jax.ShapeDtypeStruct(lead + (s,), i32),
        }
        if cfg.input_kind == "vlm":
            specs["patch_embeds"] = jax.ShapeDtypeStruct(
                lead + (cfg.n_patches, cfg.d_model), cfg.dtype
            )
        return specs
    if shape.kind == "prefill":
        b = shape.global_batch
        specs = {"tokens": jax.ShapeDtypeStruct((b, shape.seq_len), i32)}
        if cfg.input_kind == "vlm":
            specs["patch_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.n_patches, cfg.d_model), cfg.dtype
            )
        return specs
    # decode: one new token against a seq_len-deep cache/state
    b = shape.global_batch
    return {
        "tokens": jax.ShapeDtypeStruct((b, 1), i32),
        "pos": jax.ShapeDtypeStruct((), i32),
    }
