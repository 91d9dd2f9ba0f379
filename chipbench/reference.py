"""What the plain references of the architectures share.

Each architecture (``chipbench/arch/<arch>.py``) writes its model out in
``jax.numpy``, float32 at ``highest`` matmul precision, with no kernel,
cache, paging or batching, from the helpers here, and imports nothing of
the server.  ``gaps`` runs such a model over one prompt with the tokens
the server served after it.

ForkKV's fork semantics are part of the model served: a request forked from
a session's document inherits the document's base K/V as the session's own
pass computed them (under the session's adapter), and adds the LoRA
residual of its own pass.  A model function computes the session's pass
first and takes the document's base K/V from it.

``gaps`` returns, at each served position, how far the served token's logit
lies below the reference's best.  Greedy serving that computes the same
function reads about 0 there, up to rounding where the two best logits
nearly tie.

The control (``control=True``) is the same model computed with every weight
matrix rounded to int8 (symmetric, one scale per output channel): at each of
the same positions it reads the gap of the token the int8 model puts first.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512                    # query rows per attention block


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, theta):
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


@functools.partial(jax.jit,
                   static_argnames=("model", "dims", "control", "shared"))
def _gaps(params, lora, adapter, tokens, idx, served, doc, n_doc, *,
          model: Callable, dims, control: bool, shared: bool):
    ref = model(params, lora, adapter, tokens, idx, doc, n_doc, dims,
                False, shared)
    best = jnp.max(ref, axis=-1)
    program = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    if not control:
        return program, program
    low = model(params, lora, adapter, tokens, idx, doc, n_doc, dims, True,
                shared)
    pick = jnp.argmax(low, axis=-1)
    return program, best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]


def gaps(model: Callable, params, lora, dims, adapter: int,
         prompt: Sequence[int], served: Sequence[int], doc: Sequence[int],
         s_pad: int, t_pad: int, control: bool = False
         ) -> Tuple[np.ndarray, np.ndarray]:
    """Per served token: (the served token's gap below the reference's
    best logit, the gap of the int8 control's pick; equal to the first
    unless ``control``).  ``model(params, lora, adapter, tokens, idx, doc,
    n_doc, dims, quant, shared)`` gives float32 logits at positions
    ``idx`` of ``tokens``, its weights rounded to int8 with ``quant``.
    ``doc``: the session document the request forked from, its prefix
    (empty when it forked from none).  Sequences are padded to ``s_pad``
    positions and the served tokens to ``t_pad``, so one compiled
    reference serves every request of a cell."""
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
                       jnp.asarray(docs), jnp.int32(len(doc)), model=model,
                       dims=dims, control=control, shared=bool(doc))
    return np.asarray(prog)[:n], np.asarray(ctrl)[:n]
