"""Seeded random weights, made on the device in the form they are served in.

Every projection is int4: its codes come from random bytes, two nibbles a
byte, each nibble a two's-complement code in [-8, 7] with -8 mapped to 0,
so the codes are symmetric on [-7, 7] like a symmetric int4 grid. Row 2k
of a [K, N] projection is the low nibble of byte row k, row 2k+1 the high
nibble. Each output channel has a float32 scale. Embedding, norms and the
output head are bfloat16. There is no calibration and no float master
copy: the values are random, the shapes and the work are the model's.

`layer_codes` and `head_weights` rebuild the very same numbers one layer
at a time for the reference, from the seed alone.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

PROJS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
# E[q^2] of the code distribution above: (2 * (1 + 4 + ... + 49)) / 16
CODE_MEAN_SQ = 17.5


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    n_layers_published: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    rms_eps: float
    block_size: int
    kv_bits: int
    logit_std: float

    def proj_shape(self, name: str) -> tuple[int, int]:
        d, qd, kd = self.d_model, self.n_heads * self.head_dim, \
            self.n_kv_heads * self.head_dim
        return {"wq": (d, qd), "wk": (d, kd), "wv": (d, kd), "wo": (qd, d),
                "w_gate": (d, self.d_ff), "w_up": (d, self.d_ff),
                "w_down": (self.d_ff, d)}[name]


def dims_of(config: dict) -> Dims:
    m, e, w = config["model"], config["engine"], config["weights"]
    return Dims(n_layers=m["num_hidden_layers"],
                n_layers_published=config["deployment"]["layers_published"],
                d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
                n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
                d_ff=m["intermediate_size"], vocab=m["vocab_size"],
                rope_theta=float(m["rope_theta"]), rms_eps=m["rms_norm_eps"],
                block_size=e["block_size"], kv_bits=e["kv_bits"],
                logit_std=w["logit_std"])


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number (the seed may exceed 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _proj_key(key, layer, i):
    return jax.random.fold_in(jax.random.fold_in(key, layer), i)


def _nibble_code(n):
    """4-bit two's complement → int8 code, with -8 mapped to 0."""
    c = jnp.where(n >= 8, n - 16, n)
    return jnp.where(c == -8, 0, c)


def _proj_bytes(key, k: int, n: int):
    """Packed [K/2, N] uint8 (nibble -8 already mapped to 0) and the
    per-channel scale multiplier in [0.75, 1.25)."""
    kb, ks = jax.random.split(key)
    raw = jax.random.bits(kb, (k // 2, n), jnp.uint8).astype(jnp.int32)
    lo = _nibble_code(raw & 0xF)
    hi = _nibble_code(raw >> 4)
    packed = ((lo & 0xF) | ((hi & 0xF) << 4)).astype(jnp.uint8)
    mult = jax.random.uniform(ks, (n,), jnp.float32, 0.75, 1.25)
    return packed, lo, hi, mult


def _base_scale(dims: Dims, name: str) -> float:
    k = dims.proj_shape(name)[0]
    s = 1.0 / math.sqrt(CODE_MEAN_SQ * k)
    if name in ("wo", "w_down"):
        # residual projections: each of the published model's layers adds a
        # small part to the residual stream (GPT-2's 1/sqrt(2 L) rule)
        s /= math.sqrt(2 * dims.n_layers_published)
    return s


def _layer_packed(key, dims: Dims, layer):
    out = {}
    for i, name in enumerate(PROJS):
        k, n = dims.proj_shape(name)
        packed, _, _, mult = _proj_bytes(_proj_key(key, layer, i), k, n)
        out[name] = {"packed": packed, "scale": mult * _base_scale(dims, name)}
    return out


def layer_codes(key, dims: Dims, layer: int) -> dict:
    """One layer's projections as int8 codes [K, N] and f32 scales [N] —
    the reference's view of the numbers `make_params` packs."""
    out = {}
    for i, name in enumerate(PROJS):
        k, n = dims.proj_shape(name)
        _, lo, hi, mult = _proj_bytes(_proj_key(key, layer, i), k, n)
        codes = jnp.stack([lo, hi], axis=1).reshape(k, n).astype(jnp.int8)
        out[name] = (codes, mult * _base_scale(dims, name))
    return out


def head_weights(key, dims: Dims):
    """(embedding [V, d], output head [d, V]) in bfloat16."""
    ke, kh = jax.random.split(jax.random.fold_in(key, 1 << 20))
    embed = jax.random.normal(ke, (dims.vocab, dims.d_model), jnp.float32)
    head = jax.random.normal(kh, (dims.d_model, dims.vocab), jnp.float32) \
        * (dims.logit_std / math.sqrt(dims.d_model))
    return embed.astype(jnp.bfloat16), head.astype(jnp.bfloat16)


def make_params(seed: int, dims: Dims):
    """The served parameter tree, in the layout `pack_dense_params`
    documents, made on the default device by one jitted call."""
    def build(key):
        layers = jax.vmap(lambda l: _layer_packed(key, dims, l))(
            jnp.arange(dims.n_layers))
        embed, head = head_weights(key, dims)
        ones = jnp.ones((dims.n_layers, dims.d_model), jnp.bfloat16)
        return {
            "embed": embed,
            "final_norm": {"scale": jnp.ones((dims.d_model,), jnp.bfloat16)},
            "lm_head": head,
            "layers": {
                "attn_norm": {"scale": ones}, "ffn_norm": {"scale": ones},
                "attn": {n: layers[n] for n in ("wq", "wk", "wv", "wo")},
                "ffn": {n: layers[n] for n in ("w_gate", "w_up", "w_down")},
            },
        }
    return jax.jit(build)(seed_key(seed))
