"""kernel.wkv.roofline: the RWKV-6 WKV kernels' share, in %, of their
roofline: the least time their work needs on the chip over the summed
device time of their events (forward, forward that keeps each chunk's
starting state, and backward).

Each event is one layer's call.  Its least time is the larger of its MXU
FLOPs over the bf16 peak and its HBM bytes over the peak bandwidth; here
the bytes bound it (about 0.1 GB against about 5 GFLOP a call at the
cell's shapes), and the kernel's own bound, the VPU and EUP work of its
decays, is neither, so the share reads well under 100 %.

Counted from the configuration's shapes and the kernel's chunk (64) and
sub-chunk (32) at them.  Bytes: what the kernel moves, inputs read once
and outputs written once in their dtypes: r, k, v and the log decay in
the parameter dtype, the float32 bonus and states, and per chunk a
float32 N x N starting state, written by the forward that keeps them and
read by the backward.  FLOPs: its dots per (batch, head, chunk):
triangular-ones cumulative sums of the log decay (two forward, four
backward), the readout of the carried state and its update, the
cross-sub-chunk scores and their products, and the in-sub-chunk scores
times v (the in-sub-chunk decays run on the VPU).
"""
from pathlib import Path

from benchlib import trace as tr
from benchlib.files import Bench

CHUNK = 64
SUB_CHUNK = 32
ALIGN = 16           # the kernel pads the sequence to whole chunks
F32 = 4
KINDS = ("wkv_fwd_states", "wkv_fwd", "wkv_bwd")   # by the kernels' names


def shapes(config: dict, traffic: dict) -> dict:
    """Batch, padded length, heads, head size, chunk, sub-chunk and the
    parameter itemsize of one layer's call."""
    n = config["head_size"]
    l = traffic["seq"]
    c = min(CHUNK, -(-l // ALIGN) * ALIGN)
    return {"b": traffic["per_node_batch"], "l": -(-l // c) * c,
            "h": config["hidden_size"] // n, "n": n, "c": c,
            "sc": SUB_CHUNK if c % SUB_CHUNK == 0 else c,
            "itemsize": 2 if config["dtype"] == "bfloat16" else 4}


def flops(kind: str, s: dict) -> int:
    c, sc, n = s["c"], s["sc"], s["n"]
    subs = c // sc
    cross = sc * sc * n * subs * (subs - 1) // 2   # Σ over sub-chunks of sc·t0·N
    if kind == "wkv_bwd":
        macs = 4 * c * c * n + 4 * c * n * n + 5 * cross
    else:
        macs = 2 * c * c * n + 2 * c * n * n + subs * sc * sc * n + 2 * cross
    return 2 * macs * s["b"] * s["h"] * (s["l"] // c)


def bytes_moved(kind: str, s: dict) -> int:
    b, h, n = s["b"], s["h"], s["n"]
    seq = b * s["l"] * h * n * s["itemsize"]         # one (B, L, H, N) operand
    state = b * h * n * n * F32
    states = state * (s["l"] // s["c"])
    bonus = h * n * F32
    if kind == "wkv_bwd":
        # r, k, v, log decay, do in; dr, dk, dv, d log decay out; states in;
        # final state's gradient in, first state's out; bonus in, its
        # per-batch gradient out
        return 9 * seq + states + 2 * state + bonus + b * bonus
    out = 4 * seq + bonus + state + seq + state
    return out + (states if kind == "wkv_fwd_states" else 0)


def kind_of(name: str):
    return next((k for k in KINDS if k in name), None)


def read(ctx):
    events = [(e - s, kind_of(tr.short(text)))
              for d in ctx.devices for s, e, text in tr.window_ops(ctx.trace, d)]
    events = [(secs, kind) for secs, kind in events if kind is not None]
    if not events:
        return None
    traffic = Bench(Path(__file__).resolve().parents[1]).traffic(ctx.cell["traffic"])
    s = shapes(ctx.config, traffic)
    least = sum(max(flops(kind, s) / ctx.peaks["bf16_flops"],
                    bytes_moved(kind, s) / ctx.peaks["hbm_bytes_per_s"])
                for _, kind in events)
    return 100.0 * least / sum(secs for secs, _ in events)
