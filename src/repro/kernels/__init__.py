"""Pallas TPU kernels for the compute hot-spots (validated in interpret mode).

flash_attention  online-softmax attention, MXU-aligned VMEM tiles, GQA/window
gossip_update    fused momentum-SGD + weighted neighbor average (gossip apply)
stats            blocked L2-norm reduction (the DBench per-tensor probe)
wkv              RWKV-6's WKV recurrence, forward and backward, state in VMEM

Each has a pure-jnp oracle in ``ref.py``; ``ops.py`` holds the public jitted
wrappers (interpret=True automatically off-TPU).
"""
from repro.kernels import ops, ref
from repro.kernels.ops import flash_attention, gossip_update, l2_norms
