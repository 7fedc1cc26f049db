"""Operations and bytes from shapes: the model step's, and each kernel's.

A model step is counted as 3x its forward pass (forward, and a backward of
twice the forward's matmuls), with nothing recomputed counted.  Causal
sequence mixing (attention scores and values; the SSD's quadratic form
within a chunk) counts the half of the square at or below the diagonal.
Elementwise work is left out.  A configuration of a family not here brings
``chipbench/flops_<reference>.py`` with ``per_token(spec, seq)``.
"""
from __future__ import annotations

from chipbench.harness import BENCH_DIR, load_module


def _mamba2_ssd_dims(spec):
    d = spec["d_model"]
    di = spec["expand"] * d
    return d, di, spec["d_state"], spec["ngroups"], di // spec["headdim"], \
        spec["headdim"]


def mamba2_forward_per_token(spec: dict, seq: int) -> float:
    d, di, n, g, H, p = _mamba2_ssd_dims(spec)
    k, V, L = spec["d_conv"], spec["vocab_size"], spec["n_layer"]
    c = min(spec["chunk_size"], seq)
    proj = 2 * d * (2 * di + 2 * g * n + H) + 2 * di * d
    conv = 2 * k * (di + 2 * g * n)
    # within a chunk: C.B over the causal half (per group) and its product
    # with x (per head); across chunks: the state update and its readout
    ssd = g * c * n + H * (c * p + 4 * n * p)
    return L * (proj + conv + ssd) + 2 * d * V


def qwen2_forward_per_token(spec: dict, seq: int) -> float:
    d, V, L = spec["hidden_size"], spec["vocab_size"], spec["num_hidden_layers"]
    H, Hkv, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                  spec["head_dim"])
    ff = spec["intermediate_size"]
    proj = 2 * d * H * hd * 2 + 2 * 2 * d * Hkv * hd
    attn = 2 * 2 * (seq / 2) * H * hd          # scores and values, causal
    mlp = 3 * 2 * d * ff
    return L * (proj + attn + mlp) + 2 * d * V


_FORWARD = {"mamba2": mamba2_forward_per_token,
            "qwen2": qwen2_forward_per_token}


def model_flops_per_token(spec: dict, seq: int) -> float:
    """Forward and backward operations per token of a training step."""
    fam = spec["reference"]
    if fam in _FORWARD:
        return 3.0 * _FORWARD[fam](spec, seq)
    return load_module(BENCH_DIR / f"flops_{fam}.py").per_token(spec, seq)


# ---------------------------------------------------------------------------
# kernels, per token of the sequence they run over
# ---------------------------------------------------------------------------
def ssd_scan_per_token_layer(spec: dict, seq: int, x_bytes: int = 2
                             ) -> tuple:
    """``kernels/ssd_scan.py``, per token and layer: (operations, bytes).

    Its matmuls per chunk of c and head: C.B^T (2c^2 n), the masked product
    with x*dt (2c^2 p), the entering state's readout (2 c n p) and the state
    update (2 c n p); the prefix sums of dt*A are not counted.  It reads
    x*dt and dt*A (twice) in f32 and B, C (broadcast to every head) in the
    model dtype, and writes y in the model dtype."""
    d, di, n, g, H, p = _mamba2_ssd_dims(spec)
    c = min(spec["chunk_size"], seq)
    ops = H * (2 * c * n + 2 * c * p + 4 * n * p)
    nbytes = H * (4 * p + 2 * 4 + 2 * n * x_bytes + p * x_bytes)
    return ops, nbytes
