"""Plain float32 reference of the InternLM2 decoder (arXiv:2403.17297), as
the repository models it: pre-norm RMSNorm blocks, GQA attention with
rotate-half RoPE (theta 1e6), SwiGLU MLP, untied output head.

It imports nothing of the program.  It reads the benchmark-made weight
tree by the program's leaf names.  Departures from the published model,
which the program shares: token embeddings are scaled by sqrt(d_model)
(with random weights, a rescaling of the embedding table), the norm
epsilon is 1e-6 (published 1e-5), and q/k/v are separate matrices
(published: one fused wqkv, a layout change only).

``quant="fp8"`` is the control: every matrix product takes its inputs
rounded to float8 e4m3 with one scale per tensor, the precision below the
configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
EPS = 1e-6


def _q(x, quant):
    if quant != "fp8":
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(spec, a, b, quant):
    return jnp.einsum(spec, _q(a, quant), _q(b, quant),
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos[:, None].astype(F32) * freqs  # (L, d/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden(params, tokens, model: dict, quant=None):
    """Final-norm hidden states (L, d) of one causal sequence."""
    d, h, kv = model["d_model"], model["n_heads"], model["n_kv_heads"]
    hd = d // h
    L = tokens.shape[0]
    pos = jnp.arange(L)
    x = params["embed"]["embedding"][tokens].astype(F32) * math.sqrt(d)
    causal = jnp.tril(jnp.ones((L, L), bool))

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(F32), p)
        a = _rms(x, p["norm1"]["scale"])
        q = _rope(_mm("ld,dhk->lhk", a, p["attn"]["wq"], quant), pos, model["rope_theta"])
        k = _rope(_mm("ld,dhk->lhk", a, p["attn"]["wk"], quant), pos, model["rope_theta"])
        v = _mm("ld,dhk->lhk", a, p["attn"]["wv"], quant)
        qg = q.reshape(L, kv, h // kv, hd)
        s = _mm("qkgd,skd->kgqs", qg, k, quant) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        o = _mm("kgqs,skd->qkgd", w, v, quant).reshape(L, h, hd)
        x = x + _mm("lhk,hkd->ld", o, p["attn"]["wo"], quant)
        a = _rms(x, p["norm2"]["scale"])
        g = _mm("ld,df->lf", a, p["mlp"]["w_gate"], quant)
        u = _mm("ld,df->lf", a, p["mlp"]["w_in"], quant)
        x = x + _mm("lf,fd->ld", jax.nn.silu(g) * u, p["mlp"]["w_out"], quant)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["stack"]["groups"][0])
    return _rms(x, params["final_norm"]["scale"].astype(F32))


@functools.partial(jax.jit, static_argnames=("model_items", "quant"))
def _logits_at(params, tokens, at, model_items, quant):
    model = dict(model_items)
    x = hidden(params, tokens, model, quant)[at]
    return _mm("kd,dv->kv", x, params["embed"]["unembed"].astype(F32), quant)


def logits_at(params, tokens, at, model: dict, quant=None):
    """Logits (K, V) at positions ``at`` of the causal sequence ``tokens``
    (padding after the last real token does not reach them)."""
    keys = ("d_model", "n_heads", "n_kv_heads", "rope_theta")
    return _logits_at(params, tokens, at, tuple((k, model[k]) for k in keys), quant)
