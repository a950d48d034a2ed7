"""Plain float32 reference of BERT-Large as the repository trains it
(Devlin et al. 2018 widths): learned positions added to token embeddings
scaled by sqrt(d_model), 24 pre-norm blocks of bidirectional multi-head
attention and a GELU (tanh) MLP, a final LayerNorm, the output head tied
to the embedding, and next-token cross-entropy on synthetic tokens.

Then one DropCompute step as the paper's Algorithm 1 states it: a worker
keeps micro-batch m while the running sum of its latencies stays below
tau (the first is always kept); the summed gradient of the kept rows is
divided by their token count, clipped to global norm 1, and applied by
AdamW.

It imports nothing of the program and reads the benchmark-made weights
by the program's leaf names.  Departures from the published BERT, which
the program shares: pre-norm instead of post-norm blocks, no biases in
the projections, a causal next-token objective in place of masked-LM,
and a position table of 8,192 rows (published 512; rows past the
sequence are never read).

``quant="fp8"`` is the control: the inputs of every matrix product are
rounded to float8 e4m3 with one scale per tensor.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

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


def _ln(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def loss_sums(params, tokens, weights, heads: int, quant=None):
    """(sum of weighted next-token cross-entropy, sum of weights)."""
    params = jax.tree.map(lambda a: a.astype(F32), params)
    emb = params["embed"]["embedding"]
    d = emb.shape[1]
    b, s = tokens.shape
    hd = d // heads
    x = emb[tokens] * math.sqrt(d) + params["embed"]["pos_embedding"][:s]

    def layer(x, p):
        a = _ln(x, p["norm1"])
        q = _mm("bsd,dhk->bshk", a, p["attn"]["wq"], quant)
        k = _mm("bsd,dhk->bshk", a, p["attn"]["wk"], quant)
        v = _mm("bsd,dhk->bshk", a, p["attn"]["wv"], quant)
        w = jax.nn.softmax(_mm("bqhk,bshk->bhqs", q, k, quant) / math.sqrt(hd), -1)
        o = _mm("bhqs,bshk->bqhk", w, v, quant)
        x = x + _mm("bshk,hkd->bsd", o, p["attn"]["wo"], quant)
        a = _ln(x, p["norm2"])
        x = x + _mm("bsf,fd->bsd", _gelu(_mm("bsd,df->bsf", a, p["mlp"]["w_in"], quant)),
                    p["mlp"]["w_out"], quant)
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["stack"]["groups"][0])
    x = _ln(x, params["final_norm"])
    logits = _mm("bsd,vd->bsv", x[:, :-1], emb, quant)
    lse = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    w = weights[:, 1:]
    return jnp.sum((lse - tgt) * w), jnp.sum(w)


def keep_mask(latencies: np.ndarray, tau: float) -> np.ndarray:
    """(W, M) 1 where the worker computes the micro-batch."""
    keep = np.cumsum(latencies, -1) < tau
    keep[:, 0] = True
    return keep


def leaf_norms(tree):
    """Norm of every leaf, and of every layer of a stacked leaf."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(F32)
        stacked = any(getattr(p, "key", None) == "groups" for p in path)
        if stacked:
            out.append(jnp.sqrt(jnp.sum(x.reshape(x.shape[0], -1) ** 2, -1)))
        else:
            out.append(jnp.sqrt(jnp.sum(x ** 2))[None])
    return jnp.concatenate(out)


@functools.partial(jax.jit, static_argnames=("heads", "quant"))
def _grad_chunk(params, tokens, weights, heads, quant):
    (ls, ws), g = jax.value_and_grad(
        lambda p: loss_sums(p, tokens, weights, heads, quant), has_aux=True)(params)
    return ls, ws, g


@jax.jit
def _adamw(params, m, v, count, grads, w_sum, lr, b1, b2, eps, wd, clip):
    grads = jax.tree.map(lambda g: g / jnp.maximum(w_sum, 1.0), grads)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(lambda g: g * jnp.minimum(1.0, clip / (norm + 1e-9)), grads)
    count = count + 1
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count
    params = jax.tree.map(
        lambda p, m_, v_: p - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps) + wd * p),
        params, m, v)
    return params, m, v, count, grads


def train_steps(params, batches, latencies, job: dict, heads: int, devices,
                quant=None, rows_per_chunk: int = 16):
    """Follow the first steps of the job.  Returns the loss of each step,
    the leaf norms of the first (clipped) gradient, and the leaf norms of
    the parameters' change over all the steps."""
    mesh = jax.sharding.Mesh(np.asarray(devices), ("data",))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    params = jax.device_put(jax.tree.map(lambda a: a.astype(F32), params), rep)
    p0 = params
    zeros = jax.tree.map(jnp.zeros_like, params)
    m, v, count = zeros, zeros, jnp.zeros((), F32)
    r_ = job["rows"]
    losses, first_grad = [], None
    for batch, lat in zip(batches, latencies):
        keep = keep_mask(np.asarray(lat), job["tau"])  # (W, M)
        row_keep = np.repeat(keep.reshape(-1), r_)  # rows are (worker, mb, row)
        idx = np.nonzero(row_keep)[0]
        pad = (-len(idx)) % rows_per_chunk
        tok = np.concatenate([batch["tokens"][idx], np.zeros((pad,) + batch["tokens"].shape[1:], np.int32)])
        wts = np.concatenate([batch["weights"][idx], np.zeros((pad,) + batch["weights"].shape[1:], np.float32)])
        g_sum, l_sum, w_sum = zeros, 0.0, 0.0
        with jax.default_matmul_precision("highest"):
            for c in range(0, len(tok), rows_per_chunk):
                t = jax.device_put(tok[c:c + rows_per_chunk], rows)
                w = jax.device_put(wts[c:c + rows_per_chunk], rows)
                ls, ws, g = _grad_chunk(params, t, w, heads, quant)
                g_sum = jax.tree.map(jnp.add, g_sum, g)
                l_sum, w_sum = l_sum + ls, w_sum + ws
            params, m, v, count, grads = _adamw(
                params, m, v, count, g_sum, w_sum, job["lr"], 0.9, 0.999, 1e-8,
                job["weight_decay"], job["clip_norm"])
        losses.append(float(l_sum / jnp.maximum(w_sum, 1.0)))
        if first_grad is None:
            first_grad = np.asarray(leaf_norms(grads))
    change = np.asarray(leaf_norms(jax.tree.map(jnp.subtract, params, p0)))
    return losses, first_grad, change
