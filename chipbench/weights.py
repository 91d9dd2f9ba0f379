"""Random weights and adapters for a configuration, made on the device.

One jitted call per run makes every weight from the seed in the dtype it is
served in.  The layout is the one the server takes (stacked ``(L, ...)``
layers; LoRA factors stacked ``(L, adapters, ...)``); the plain reference
(``chipbench/reference.py``) reads the same arrays.  The RMSNorm gains are 1,
as a freshly initialised checkpoint has them: the server stores a gain as
``1 + w``, so its ``w`` is 0, and the reference uses 1.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


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


def key_of(seed: int) -> jax.Array:
    """A JAX key from any non-negative seed (the driver's exceed 32 bits)."""
    word = int(np.random.default_rng([seed, 3]).integers(0, 2 ** 31 - 1))
    return jax.random.PRNGKey(word)


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
