"""Dense decoder forward pass in float32, written from the published
descriptions of Qwen3 and Llama (Yi), with no cache, kernel or batching.

Per layer: RMSNorm, q/k/v projections, (Qwen3 only) RMSNorm over each head
of q and k, rotary embedding on the two halves of each head
(``rotate_half``), causal softmax attention with each KV head shared by
``num_attention_heads / num_key_value_heads`` query heads, the output
projection and a residual add; then RMSNorm, the SwiGLU MLP
(``down(silu(gate(x)) * up(x))``) and a residual add.  A final RMSNorm and
the output head (the embedding's transpose when tied) give the logits.

Every matrix product runs at ``Precision.HIGHEST`` inside
``jax.default_matmul_precision("highest")``: on a TPU a float32 product is
otherwise computed from bfloat16 passes.  Weights are read in their served
dtype and widened to float32 one layer at a time.

``quant="fp8"`` is the control: every product with a weight takes both
operands rounded to float8 e4m3 (the weight scaled per tensor, the
activation per row), the precision step below the served bfloat16.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..weights import has_qk_norm, static_shape

HIGHEST = lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = float(jnp.finfo(F8).max)


def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale == 0.0, 1.0, scale)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _matmul(spec: str, x, w, quant: str | None):
    """``einsum(spec, x, w)`` in float32, with ``w`` a weight."""
    if quant == "fp8":
        x = _fp8(x, -1)
        w = _fp8(w, None)
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def _rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [B, S, H, Dh] at positions 0..S-1."""
    s, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # [S, half]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(hf: dict, quant, x, lw):
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    eps = hf["rms_norm_eps"]
    h = _rms_norm(x, lw["attn_norm"], eps)
    q = _matmul("bsd,dhk->bshk", h, lw["wq"], quant)
    k = _matmul("bsd,dhk->bshk", h, lw["wk"], quant)
    v = _matmul("bsd,dhk->bshk", h, lw["wv"], quant)
    if has_qk_norm(hf):
        q = _rms_norm(q, lw["q_norm"], eps)
        k = _rms_norm(k, lw["k_norm"], eps)
    q = _rope(q, hf["rope_theta"])
    k = _rope(k, hf["rope_theta"])
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
    s = s / math.sqrt(q.shape[-1])
    n = s.shape[-1]
    causal = jnp.tril(jnp.ones((n, n), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
    x = x + _matmul("bshk,hkd->bsd", o, lw["wo"], quant)
    h = _rms_norm(x, lw["mlp_norm"], eps)
    g = _matmul("bsd,df->bsf", h, lw["w_gate"], quant)
    u = _matmul("bsd,df->bsf", h, lw["w_up"], quant)
    x = x + _matmul("bsf,fd->bsd", jax.nn.silu(g) * u, lw["w_down"], quant)
    return x, None


@partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _block(hf_items: tuple, first: int, count: int, quant, w, tokens):
    hf = dict(hf_items)
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    x, _ = lax.scan(partial(_layer, hf, quant), x, w["layers"])
    x = _rms_norm(x[:, first:first + count],
                  w["final_norm"].astype(jnp.float32), hf["rms_norm_eps"])
    head = w["embed"].T if hf["tie_word_embeddings"] else w["head"]
    return _matmul("bsd,dv->bsv", x, head.astype(jnp.float32), quant)


def logits(hf: dict, w: dict, tokens, first: int, count: int, *,
           quant: str | None = None, rows_per_block: int = 4):
    """Float32 logits ``[B, count, vocab]`` at positions
    ``first .. first+count-1`` of ``tokens [B, S]`` (each row its own
    sequence from position 0), computed ``rows_per_block`` rows at a time."""
    static_shape(hf)  # the configuration names every size it needs
    items = tuple(sorted((k, v) for k, v in hf.items()
                         if isinstance(v, (int, float, str, bool))))
    tokens = jnp.asarray(tokens, jnp.int32)
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, tokens.shape[0], rows_per_block):
            rows = tokens[i:i + rows_per_block]
            pad = rows_per_block - rows.shape[0]
            if pad:  # one compiled shape for every block
                rows = jnp.pad(rows, ((0, pad), (0, 0)))
            out.append(_block(items, first, count, quant, w, rows)
                       [:rows_per_block - pad])
    return jnp.concatenate(out, axis=0)
