"""Operations and bytes that a unit of work needs, from its shapes alone.

These are the yardstick of the roofline shares: they count the work a
request asks for, not what an implementation happens to move, so a later
change to the program is judged against the same work.
"""

from __future__ import annotations

from typing import Mapping

F32 = 4
BF16 = 2


def overlay_bytes(n_in: int, n_out: int, items: int) -> int:
    """HBM bytes an OpenCL kernel over ``items`` work-items must move: each
    of the kernel's own inputs read once and each output written once, as
    float32, whatever padding an executor adds."""
    return (n_in + n_out) * items * F32


def _layer_matmul_params(c: Mapping) -> int:
    d, hd, hq, hkv, ff = (c["d_model"], c["head_dim"], c["n_heads"],
                          c["n_kv_heads"], c["d_ff"])
    attn = d * hd * (hq + 2 * hkv) + hq * hd * d
    mlp = (3 if c["activation"] == "swiglu" else 2) * d * ff
    return attn + mlp


def dense_weight_bytes(c: Mapping) -> int:
    """Bytes of every weight of a dense decoder: per layer the attention
    and MLP projections and two norms; the embedding, the unembedding and
    the final norm once."""
    d, v = c["d_model"], c["vocab_padded"]
    per_layer = _layer_matmul_params(c) + 2 * d
    return (c["n_layers"] * per_layer + 2 * v * d + d) * BF16


def dense_decode_step(c: Mapping, batch: int, kv_len: int,
                      chips: int = 1) -> dict:
    """FLOPs and HBM bytes, per chip, of one decode step that feeds one
    token to each of ``batch`` sequences holding ``kv_len`` positions each
    (the new one included), with every layer shared by ``chips`` chips.

    Bytes: every weight once, except that the embedding table is read only
    at the ``batch`` rows gathered; the keys and values of the positions
    held (not the padded cache length), and the new ones written.  FLOPs:
    the projections, the unembedding and attention over ``kv_len``."""
    d, hd, hq, hkv, L, v = (c["d_model"], c["head_dim"], c["n_heads"],
                            c["n_kv_heads"], c["n_layers"],
                            c["vocab_padded"])
    weights = dense_weight_bytes(c) - v * d * BF16 + batch * d * BF16
    kv = 2 * L * batch * hkv * hd * (kv_len + 1) * BF16
    flops = 2 * batch * (L * _layer_matmul_params(c) + d * v) \
        + 4 * L * batch * hq * hd * kv_len
    return dict(flops=flops / chips, bytes=(weights + kv) / chips)


def least_seconds(flops: float, nbytes: float, peaks: Mapping) -> float:
    """The least time the chip could take: the larger of the operations
    over peak FLOP/s and the bytes over peak HBM bandwidth."""
    return max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bw"])
