"""The plain reference the served tokens are judged against.

A dense decoder written out in ``jax.numpy``, float32 at ``highest`` matmul
precision, with no kernel, cache, paging or batching: RMSNorm, q/k/v
projections each with its adapter's LoRA offset (``alpha / rank * x A B``),
split-half RoPE on q and k, causal grouped-query attention (query head ``h``
reads KV head ``h // (heads / kv_heads)``), a SwiGLU MLP, a final RMSNorm and
an untied unembedding.  It imports nothing of the server.

ForkKV's fork semantics are part of the model served: a request forked from
a session's document inherits the document's base K/V (``x W_k``, ``x W_v``)
as the session's own pass computed them (under the session's adapter), and
adds the LoRA residual of its own pass.  The reference computes the
session's pass first and takes the document's base K/V from it.

``gaps`` runs one prompt with the tokens the server served after it and
returns, at each served position, how far the served token's logit lies
below the reference's best.  Greedy serving that computes the same function
reads about 0 there, up to rounding where the two best logits nearly tie.

The control (``control=True``) is the same model computed with every weight
matrix rounded to int8 (symmetric, one scale per output channel): at each of
the same positions it reads the gap of the token the int8 model puts first.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.weights import Dims

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512                    # query rows per attention block
MATS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x: (S, H, D), positions 0..S-1, split-half pairs."""
    s, _, d = x.shape
    half = d // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def int8_round(w):
    """Symmetric int8 with one scale per output channel (last axis),
    returned dequantized in float32."""
    w = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True),
                        1e-30) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _forward(params, lora, adapter, tokens, idx, dims: Dims, quant: bool,
             shared=None, n_shared=0):
    """float32 logits at positions ``idx`` of ``tokens`` (S,), and each
    layer's base projections ``(x W_k, x W_v)`` of every position.  With
    ``shared`` (a session's base projections, (L, S, kv_dim) each), the
    first ``n_shared`` positions take their base K/V from it."""
    f32 = jnp.float32
    cast = int8_round if quant else (lambda w: w.astype(f32))
    hq, hkv, hd = dims.heads, dims.kv_heads, dims.head_dim
    group = hq // hkv
    s = tokens.shape[0]
    scaling = dims.alpha / dims.rank
    x = params["embed"][tokens].astype(f32)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    inherited = (jnp.arange(s) < n_shared)[:, None]

    def lora_off(h, a, b):
        return scaling * _mm(_mm(h, a.astype(f32)), b.astype(f32))

    def layer(x, w):
        p, lo, base = w
        h = _rms(x, dims.norm_eps)
        q = _mm(h, cast(p["wq"])) + lora_off(h, lo["a_q"], lo["b_q"])
        kb, vb = _mm(h, cast(p["wk"])), _mm(h, cast(p["wv"]))
        if base is not None:
            kb = jnp.where(inherited, base[0], kb)
            vb = jnp.where(inherited, base[1], vb)
        k = kb + lora_off(h, lo["a_k"], lo["b_k"])
        v = vb + lora_off(h, lo["a_v"], lo["b_v"])
        q = _rope(q.reshape(s, hq, hd), dims.rope_theta)
        k = _rope(k.reshape(s, hkv, hd), dims.rope_theta)
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v.reshape(s, hkv, hd), group, axis=1)
        outs = []
        for q0 in range(0, s, Q_BLOCK):
            qb = q[q0:q0 + Q_BLOCK]
            sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * hd ** -0.5
            sc = jnp.where(causal[q0:q0 + Q_BLOCK][None], sc, -jnp.inf)
            pr = jax.nn.softmax(sc, axis=-1)
            outs.append(jnp.einsum("hqk,khd->qhd", pr, v, precision=HI))
        att = jnp.concatenate(outs, 0).reshape(s, hq * hd)
        x = x + _mm(att, cast(p["wo"]))
        h = _rms(x, dims.norm_eps)
        gate = jax.nn.silu(_mm(h, cast(p["w_gate"])))
        x = x + _mm(gate * _mm(h, cast(p["w_up"])), cast(p["w_down"]))
        return x, (kb, vb)

    mats = {k: params["layers"][k] for k in MATS}
    ada = {k: lora[k][:, adapter] for k in ("a_q", "b_q", "a_k", "b_k",
                                            "a_v", "b_v")}
    x, base = jax.lax.scan(layer, x, (mats, ada, shared))
    h = _rms(x[idx], dims.norm_eps)
    return _mm(h, cast(params["unembed"])), base


def _model(params, lora, adapter, tokens, idx, doc, n_doc, dims, quant,
           shared: bool):
    """Logits under ForkKV's fork semantics: a request forked from a
    session's document inherits the document's base K/V as the session's
    own pass (adapter 0) computed them, and adds its own LoRA residual."""
    base = None
    if shared:
        _, base = _forward(params, lora, 0, doc, idx, dims, quant)
    return _forward(params, lora, adapter, tokens, idx, dims, quant, base,
                    n_doc)[0]


@functools.partial(jax.jit, static_argnames=("dims", "control", "shared"))
def _gaps(params, lora, adapter, tokens, idx, served, doc, n_doc, *,
          dims: Dims, control: bool, shared: bool):
    ref = _model(params, lora, adapter, tokens, idx, doc, n_doc, dims,
                 False, shared)
    best = jnp.max(ref, axis=-1)
    program = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    if not control:
        return program, program
    low = _model(params, lora, adapter, tokens, idx, doc, n_doc, dims, True,
                 shared)
    pick = jnp.argmax(low, axis=-1)
    return program, best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]


def gaps(params, lora, dims: Dims, adapter: int, prompt: Sequence[int],
         served: Sequence[int], doc: Sequence[int], s_pad: int, t_pad: int,
         control: bool = False) -> Tuple[np.ndarray, np.ndarray]:
    """Per served token: (the served token's gap below the reference's
    best logit, the gap of the int8 control's pick; equal to the first
    unless ``control``).  ``doc``: the session document the request forked
    from, its prefix (empty when it forked from none).  Sequences are
    padded to ``s_pad`` positions and the served tokens to ``t_pad``, so
    one compiled reference serves every request of a cell."""
    seq = list(prompt) + list(served)
    n = len(served)
    if len(seq) > s_pad or n > t_pad or n == 0:
        raise ValueError(f"request of {len(seq)} tokens, {n} served, does "
                         f"not fit the reference's {s_pad}/{t_pad}")
    if list(prompt[:len(doc)]) != list(doc):
        raise ValueError("the prompt does not start with its document")
    tokens = np.zeros(s_pad, np.int32)
    tokens[:len(seq)] = seq
    docs = np.zeros(s_pad, np.int32)
    docs[:len(doc)] = doc
    idx = np.zeros(t_pad, np.int32)
    idx[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    want = np.zeros(t_pad, np.int32)
    want[:n] = served
    prog, ctrl = _gaps(params, lora, jnp.int32(adapter), jnp.asarray(tokens),
                       jnp.asarray(idx), jnp.asarray(want),
                       jnp.asarray(docs), jnp.int32(len(doc)), dims=dims,
                       control=control, shared=bool(doc))
    return np.asarray(prog)[:n], np.asarray(ctrl)[:n]
