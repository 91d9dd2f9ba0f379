"""A dense decoder with q/k/v LoRA adapters (``"arch": "dense"``).

RMSNorm, q/k/v projections each with its adapter's LoRA offset
(``alpha / rank * x A B``), split-half RoPE on q and k, causal grouped-query
attention (query head ``h`` reads KV head ``h // (heads / kv_heads)``), a
SwiGLU MLP, a final RMSNorm and an untied unembedding.

Weights are random from the seed, made on the device in one jitted call in
the dtype they are served in.  The layout is the one the server takes
(stacked ``(L, ...)`` layers; LoRA factors stacked ``(L, adapters, ...)``);
the reference reads the same arrays.  The RMSNorm gains are 1, as a freshly
initialised checkpoint has them: the server stores a gain as ``1 + w``, so
its ``w`` is 0, and the reference uses 1.

The work counts are of the work the algorithm needs, whatever implements
it.  A paged grid call of one layer needs:

- bytes: every base KV page that the step's block tables reach, each
  counted once however many requests share it; each request's own
  residual pages at their rank-r size; the queries and outputs; and each
  request's LoRA up-projections ``B_k``, ``B_v``;
- FLOPs: ``q k^T`` and ``p v`` over each query's own causal context, and
  the rank-r reconstruction ``K += r_k B_k``, ``V += r_v B_v`` of every
  context token of each request.

A grid that reads shared pages once per group can therefore not read above
100% of its roofline.  Every layer runs both grids alike.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

from chipbench import reference
from chipbench.reference import HI, Q_BLOCK, int8_round, mm, rms, rope
from chipbench.roofline import Row, live_pages
from chipbench.weights import key_of

MATS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


class Dims(NamedTuple):
    """The sizes of a dense decoder with q/k/v LoRA adapters."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rank: int
    alpha: float
    rope_theta: float
    norm_eps: float
    dtype: str
    std: float                   # dense weights
    qk_std: float                # query and key projections
    lora_b_std: float            # LoRA up-projections

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def itemsize(self) -> int:
        return jnp.dtype(self.dtype).itemsize


def dims_of(conf: Dict) -> Dims:
    """The sizes a configuration file states (Hugging Face key names)."""
    init = conf["init"]
    return Dims(
        layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or
        conf["hidden_size"] // conf["num_attention_heads"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        rank=conf["lora"]["rank"], alpha=float(conf["lora"]["alpha"]),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]), dtype=conf["torch_dtype"],
        std=float(init["std"]), qk_std=float(init["qk_std"]),
        lora_b_std=float(init["lora_b_std"]))


# ----------------------------------------------------------------- weights
@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, dims: Dims, n_adapters: int) -> Tuple[Dict, Dict]:
    dt = jnp.dtype(dims.dtype)
    L, d, r, N = dims.layers, dims.d_model, dims.rank, n_adapters
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32) * std
                ).astype(dt)

    layers = {
        "ln1": jnp.zeros((L, d), dt),
        "ln2": jnp.zeros((L, d), dt),
        "wq": normal((L, d, dims.q_dim), dims.qk_std),
        "wk": normal((L, d, dims.kv_dim), dims.qk_std),
        "wv": normal((L, d, dims.kv_dim), dims.std),
        "wo": normal((L, dims.q_dim, d), dims.std),
        "w_gate": normal((L, d, dims.d_ff), dims.std),
        "w_up": normal((L, d, dims.d_ff), dims.std),
        "w_down": normal((L, dims.d_ff, d), dims.std),
    }
    params = {"embed": normal((dims.vocab, d), dims.std),
              "final_norm": jnp.zeros((d,), dt),
              "unembed": normal((d, dims.vocab), dims.std),
              "layers": layers}
    lora = {}
    for t, out in (("q", dims.q_dim), ("k", dims.kv_dim), ("v", dims.kv_dim)):
        lora[f"a_{t}"] = normal((L, N, d, r), d ** -0.5)
        lora[f"b_{t}"] = normal((L, N, r, out), dims.lora_b_std)
    lora["scaling"] = jnp.full((L, N), dims.alpha / r, jnp.float32)
    return params, lora


def make_weights(dims: Dims, n_adapters: int, seed: int) -> Tuple[Dict, Dict]:
    """(params, lora) on the default device, ready."""
    params, lora = _make(key_of(seed), dims, n_adapters)
    jax.block_until_ready((params, lora))
    return params, lora


# ------------------------------------------------------------------ server
def model_config(conf: Dict, dims: Dims):
    """The server's ``ModelConfig`` for a configuration file: the
    program's architecture entry ``program_arch`` with every size the
    file states."""
    # the program, imported here so that the reference below imports none
    from chipbench.serve import LoRAConfig, get_config
    base = get_config(conf["program_arch"])
    return dataclasses.replace(
        base, name=conf["name"], family="dense", num_layers=dims.layers,
        d_model=dims.d_model, num_heads=dims.heads,
        num_kv_heads=dims.kv_heads, head_dim=dims.head_dim, d_ff=dims.d_ff,
        vocab_size=dims.vocab, rope_theta=dims.rope_theta,
        norm_eps=dims.norm_eps, sliding_window=0, tie_embeddings=False,
        frontend=conf["frontend"], num_patches=0, dtype=dims.dtype,
        mlp_activation="silu", kv_quant="none", num_experts=0,
        lora=LoRAConfig(rank=dims.rank, alpha=dims.alpha,
                        targets=("q", "k", "v")))


# --------------------------------------------------------------- reference
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
        return scaling * mm(mm(h, a.astype(f32)), b.astype(f32))

    def layer(x, w):
        p, lo, base = w
        h = rms(x, dims.norm_eps)
        q = mm(h, cast(p["wq"])) + lora_off(h, lo["a_q"], lo["b_q"])
        kb, vb = mm(h, cast(p["wk"])), mm(h, cast(p["wv"]))
        if base is not None:
            kb = jnp.where(inherited, base[0], kb)
            vb = jnp.where(inherited, base[1], vb)
        k = kb + lora_off(h, lo["a_k"], lo["b_k"])
        v = vb + lora_off(h, lo["a_v"], lo["b_v"])
        q = rope(q.reshape(s, hq, hd), dims.rope_theta)
        k = rope(k.reshape(s, hkv, hd), dims.rope_theta)
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
        x = x + mm(att, cast(p["wo"]))
        h = rms(x, dims.norm_eps)
        gate = jax.nn.silu(mm(h, cast(p["w_gate"])))
        x = x + mm(gate * mm(h, cast(p["w_up"])), cast(p["w_down"]))
        return x, (kb, vb)

    mats = {k: params["layers"][k] for k in MATS}
    ada = {k: lora[k][:, adapter] for k in ("a_q", "b_q", "a_k", "b_k",
                                            "a_v", "b_v")}
    x, base = jax.lax.scan(layer, x, (mats, ada, shared))
    h = rms(x[idx], dims.norm_eps)
    return mm(h, cast(params["unembed"])), base


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


def gaps(params, lora, dims: Dims, adapter: int, prompt: Sequence[int],
         served: Sequence[int], doc: Sequence[int], s_pad: int, t_pad: int,
         control: bool = False):
    """``reference.gaps`` of this model."""
    return reference.gaps(_model, params, lora, dims, adapter, prompt,
                          served, doc, s_pad, t_pad, control=control)


# ------------------------------------------------------------- work counts
def _layer_grid_work(rows: Sequence[Row], dims: Dims,
                     page: int) -> Dict[str, float]:
    """FLOPs and bytes one layer's call of a paged grid needs."""
    it = dims.itemsize
    hq, hd, r, kvd = dims.heads, dims.head_dim, dims.rank, dims.kv_dim
    unique = set()
    res_pages = q_tok = 0
    attn_ctx = recon_ctx = 0
    for row in rows:
        n = live_pages(row.extent, page)
        unique.update(row.base_pages[:n])
        res_pages += n
        q_tok += row.q_len
        # query i attends to start + i + 1 positions
        attn_ctx += row.q_len * row.start + row.q_len * (row.q_len + 1) // 2
        recon_ctx += row.extent
    base_bytes = len(unique) * 2 * dims.kv_heads * page * hd * it
    res_bytes = res_pages * 2 * page * r * it
    qo_bytes = 2 * q_tok * hq * hd * it
    adapter_bytes = len(rows) * 2 * r * kvd * it
    flops = 4 * hq * hd * attn_ctx + 4 * r * kvd * recon_ctx
    return {"flops": float(flops),
            "bytes": float(base_bytes + res_bytes + qo_bytes + adapter_bytes)}


def grid_work(rows: Sequence[Row], dims: Dims, page: int,
              kind: str) -> Dict[str, float]:
    """FLOPs and bytes of one call of the ``kind`` grid over every layer:
    each layer runs it alike."""
    work = _layer_grid_work(rows, dims, page)
    return {"flops": work["flops"] * dims.layers,
            "bytes": work["bytes"] * dims.layers}


def step_flops(rows: Sequence[Row], dims: Dims) -> float:
    """Model FLOPs of the useful (unpadded) tokens of one step: every
    projection with its LoRA offset, attention over each token's own causal
    context, and one row of logits per request."""
    d, r = dims.d_model, dims.rank
    proj = d * (2 * dims.q_dim + 2 * dims.kv_dim) + 3 * d * dims.d_ff
    lora = 3 * d * r + r * (dims.q_dim + 2 * dims.kv_dim)
    total = 0
    for row in rows:
        ctx = row.q_len * row.start + row.q_len * (row.q_len + 1) // 2
        total += dims.layers * (2 * (proj + lora) * row.q_len +
                                4 * dims.heads * dims.head_dim * ctx)
        total += 2 * d * dims.vocab
    return float(total)
