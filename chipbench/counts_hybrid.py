"""Parameters, operations and bytes of a zamba2 decode step, from the
configuration file's shapes alone (its published keys: ``hidden_size``,
``mamba_ngroups``, ``hybrid_layer_ids``, ...).

A step feeds one token to each of ``batch`` sequences.  Its bytes are
every weight once, except that each shared block is counted once per site
that runs it (one chip's fast memory cannot keep a block between sites),
and the embedding table once as the tied head plus the ``batch`` rows
gathered; the SSM and conv state of every layer read and written; the
keys and values of the positions held at each site, and the new ones
written.  The work is split by the program's scopes: ``mamba_layer``
(input norm, mixer, residual), ``shared_block`` (concat, both norms,
attention, the adapted MLP, the site linear) and ``lm`` (embedding, final
norm, head); the scopes' numbers sum to the step's.
"""

from __future__ import annotations

from typing import Mapping

from chipbench.counts import BF16, F32


def _dims(c: Mapping) -> dict:
    d = c["hidden_size"]
    di = c["mamba_expand"] * d
    gn = c["mamba_ngroups"] * c["mamba_d_state"]
    return dict(d=d, di=di, h=c["n_mamba_heads"], p=c["mamba_headdim"],
                n=c["mamba_d_state"], conv=di + 2 * gn, k=c["mamba_d_conv"],
                hq=c["num_attention_heads"], hkv=c["num_key_value_heads"],
                hd=c["attention_head_dim"], ff=c["intermediate_size"],
                r=c["adapter_rank"], v=c["vocab_size"],
                sites=len(c["hybrid_layer_ids"]))


def _mamba_matmul_params(c: Mapping) -> int:
    x = _dims(c)
    return x["d"] * (x["di"] + x["conv"] + x["h"]) + x["di"] * x["d"]


def mamba_layer_params(c: Mapping) -> int:
    """in_proj, conv weight and bias, A_log, D, dt_bias, the gated norm,
    out_proj and the layer's input norm."""
    x = _dims(c)
    bias = x["conv"] if c["use_conv_bias"] else 0
    return _mamba_matmul_params(c) + x["conv"] * x["k"] + bias \
        + 3 * x["h"] + x["di"] + x["d"]


def _block_matmul_params(c: Mapping) -> int:
    x = _dims(c)
    d, hd = x["d"], x["hd"]
    attn = c["attention_hidden_size"] * hd * (x["hq"] + 2 * x["hkv"]) \
        + x["hq"] * hd * d
    return attn + 3 * d * x["ff"]


def shared_block_params(c: Mapping) -> int:
    """The norm over the concat, attention, the MLP's norm and the MLP."""
    return _block_matmul_params(c) + c["attention_hidden_size"] \
        + c["hidden_size"]


def site_params(c: Mapping) -> int:
    """A site's rank-r adapter of the gate-up projection and its linear."""
    x = _dims(c)
    return x["r"] * (x["d"] + 2 * x["ff"]) + x["d"] * x["d"]


def param_count(c: Mapping) -> int:
    """Every parameter of the configuration; the embedding is the head."""
    x = _dims(c)
    return c["num_hidden_layers"] * mamba_layer_params(c) \
        + c["num_mem_blocks"] * shared_block_params(c) \
        + x["sites"] * site_params(c) + x["v"] * x["d"] + x["d"]


def decode_step(c: Mapping, batch: int, kv_len: int) -> dict:
    """FLOPs and HBM bytes of one decode step whose sequences hold
    ``kv_len`` positions each (the new one included), whole and by scope.

    FLOPs: the projections, the depthwise conv, the recurrence and its
    readout (5 per state element: decay, the update's multiply-add, the
    readout's multiply-add), attention over ``kv_len`` and the head."""
    x = _dims(c)
    b, L, d = batch, c["num_hidden_layers"], x["d"]
    v = (x["v"] + 255) // 256 * 256              # the table as held
    state = b * x["h"] * x["p"] * x["n"]
    conv_state = b * (x["k"] - 1) * x["conv"]
    mamba = dict(
        flops=L * (2 * b * _mamba_matmul_params(c) + 2 * b * x["k"] * x["conv"]
                   + 5 * state),
        bytes=L * (mamba_layer_params(c) * BF16 + 2 * state * F32
                   + 2 * conv_state * BF16))
    kv = 2 * b * x["hkv"] * x["hd"] * (kv_len + 1) * BF16
    block = dict(
        flops=x["sites"] * (2 * b * (_block_matmul_params(c) + site_params(c))
                            + 4 * b * x["hq"] * x["hd"] * kv_len),
        bytes=x["sites"] * ((shared_block_params(c) + site_params(c)) * BF16
                            + kv))
    lm = dict(flops=2 * b * d * v, bytes=(v * d + b * d + d) * BF16)
    scopes = dict(mamba_layer=mamba, shared_block=block, lm=lm)
    return dict(flops=sum(s["flops"] for s in scopes.values()),
                bytes=sum(s["bytes"] for s in scopes.values()),
                scopes=scopes)
