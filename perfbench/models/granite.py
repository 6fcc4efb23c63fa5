"""Dense GQA decoder (`model_type` "granite"), served W4A4 by the
program's `QuantizedDenseLM` on int8 KV pages.

Weights (`make_params`): the int4 scheme of `bench/weights.py` for the
seven projections of each layer; embedding, norms and the output head in
bfloat16. `layer_codes` and `head_weights` rebuild the very same numbers
one layer at a time for the reference.

The reference (`forward`), written out per layer (x the residual stream;
Q4, the KV quantizer and the float8 control as in `bench/reference.py`):

    h = rmsnorm(x)                          (eps from the configuration)
    q, k, v = Q4(h) @ Wq, Q4(h) @ Wk, Q4(h) @ Wv
    k, v -> kv_bits (8) per (position, head)
    q, k' rotated by RoPE (half split, theta from the configuration)
    a = softmax(q k'^T / sqrt(dh), causal) v'      (GQA: head groups)
    x = x + Q4(a) @ Wo
    h = rmsnorm(x)
    u = silu(Q4(h) @ Wg) * (Q4(h) @ Wu)
    x = x + Q4(u (I ⊗ H16/4)) @ Wd          (Sylvester H16, normalized)
    logits = rmsnorm(x) @ Whead

Streams are processed in blocks of `block` rows against a KV buffer of the
cell's longest stream, so one program serves every stream length.

The work (`step_work`), per dispatch (one fused decode program, or one
prefill chunk), per layer: the 7 projections as int4 GEMMs; 4 quantizers
(attention in, attention out, FFN in, FFN hidden with its b-point
rotation); GQA attention over int8 pages. Once per dispatch the output
head: 2·d·V per row whose logits are used (each decode row; the last row
of a finished prefill), at the bf16 peak.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench import work
from bench.weights import CODE_MEAN_SQ, proj_bytes, proj_key, seed_key

PROJS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


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


# -- weights ---------------------------------------------------------------

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
        packed, _, _, mult = proj_bytes(proj_key(key, layer, i), k, n)
        out[name] = {"packed": packed, "scale": mult * _base_scale(dims, name)}
    return out


def layer_codes(key, dims: Dims, layer: int) -> dict:
    """One layer's projections as int8 codes [K, N] and f32 scales [N] —
    the reference's view of the numbers `make_params` packs."""
    out = {}
    for i, name in enumerate(PROJS):
        k, n = dims.proj_shape(name)
        _, lo, hi, mult = proj_bytes(proj_key(key, layer, i), k, n)
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


# -- the system under test -------------------------------------------------

def arch(config: dict, dims: Dims):
    """The program's `ArchConfig` of this configuration."""
    from repro.models.config import ArchConfig
    return ArchConfig(name=config["name"], family="dense",
                      n_layers=dims.n_layers, d_model=dims.d_model,
                      vocab=dims.vocab, n_heads=dims.n_heads,
                      n_kv_heads=dims.n_kv_heads, head_dim=dims.head_dim,
                      rope_theta=dims.rope_theta, d_ff=dims.d_ff, act="silu",
                      norm="rmsnorm")


def servable(config: dict, dims: Dims, params):
    """`QuantizedDenseLM` over `params`, as `ServeEngine` takes it. The
    program is imported here, late: a checkout without it fails at the
    engine's build, not at import."""
    from repro.serve.engine import as_servable
    from repro.serve.quantized import QuantizedDenseLM
    e = config["engine"]
    qlm = QuantizedDenseLM(arch(config, dims), block_size=e["block_size"],
                           kv_bits=e["kv_bits"])
    return as_servable(qlm, params)


# -- the reference -----------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _layer_block(x, kbuf, vbuf, pos0, w, *, dims: Dims, mode: str):
    """One layer over rows [pos0, pos0 + B) of a stream whose earlier rows
    already sit in the layer's KV buffers [Tmax, KH, dh]."""
    r = functools.partial(R.round_to, mode=mode)
    b = x.shape[0]
    H, KH, dh = dims.n_heads, dims.n_kv_heads, dims.head_dim
    pos = pos0 + jnp.arange(b)
    h = r(R.rms(x, dims.rms_eps))
    q = r(R.qlin(h, w["wq"])).reshape(b, H, dh)
    k = R.kv(r(R.qlin(h, w["wk"])).reshape(b, KH, dh), dims.kv_bits)
    v = R.kv(r(R.qlin(h, w["wv"])).reshape(b, KH, dh), dims.kv_bits)
    q = r(R.rope(q, pos, dims.rope_theta))
    k = R.rope(k, pos, dims.rope_theta)
    kbuf = jax.lax.dynamic_update_slice(kbuf, k, (pos0, 0, 0))
    vbuf = jax.lax.dynamic_update_slice(vbuf, v, (pos0, 0, 0))
    g = H // KH
    qg = q.reshape(b, KH, g, dh)
    logits = jnp.einsum("bkgd,tkd->kgbt", qg, kbuf, precision=R.HI) \
        / math.sqrt(dh)
    keep = jnp.arange(kbuf.shape[0])[None, :] <= pos[:, None]
    logits = jnp.where(keep[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    a = jnp.einsum("kgbt,tkd->bkgd", p, vbuf, precision=R.HI)
    a = r(a.reshape(b, H * dh))
    x = r(x + r(R.qlin(a, w["wo"])))
    h = r(R.rms(x, dims.rms_eps))
    u = r(jax.nn.silu(r(R.qlin(h, w["w_gate"]))) * r(R.qlin(h, w["w_up"])))
    bs = dims.block_size
    rot = jnp.einsum("bnc,cd->bnd", u.reshape(b, -1, bs),
                     jnp.asarray(R.sylvester(bs)), precision=R.HI)
    x = r(x + r(R.qlin(rot.reshape(b, -1), w["w_down"])))
    return x, kbuf, vbuf


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _logits(x, head, *, dims: Dims, mode: str):
    h = R.round_to(R.rms(x, dims.rms_eps), mode)
    return R.round_to(jnp.dot(h, head.astype(jnp.float32), precision=R.HI),
                      mode)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _embed(tokens, embed, *, dims: Dims, mode: str):
    return R.round_to(jnp.take(embed, tokens, axis=0).astype(jnp.float32),
                      mode)


_layer_weights = jax.jit(layer_codes, static_argnames=("dims",))


def forward(key, dims: Dims, streams: list[list[int]], rows: list[list[int]],
            *, mode: str = "f32", block: int = 256,
            max_len: int | None = None) -> list[np.ndarray]:
    """Logits [len(rows[i]), V] at the positions `rows[i]` of each token
    stream, computed layer by layer over blocks of `block` rows."""
    t_max = max_len or max(len(s) for s in streams)
    t_max = -(-t_max // block) * block
    embed, head = head_weights(key, dims)
    xs = []
    for s in streams:
        n = -(-len(s) // block) * block
        toks = jnp.asarray(list(s) + [0] * (n - len(s)), jnp.int32)
        xs.append(_embed(toks, embed, dims=dims, mode=mode))
    del embed
    zeros = jnp.zeros((t_max, dims.n_kv_heads, dims.head_dim), jnp.float32)
    for layer in range(dims.n_layers):
        w = _layer_weights(key, dims=dims, layer=layer)
        for i, x in enumerate(xs):
            kbuf, vbuf, outs = zeros, zeros, []
            for j in range(x.shape[0] // block):
                y, kbuf, vbuf = _layer_block(
                    x[j * block:(j + 1) * block], kbuf, vbuf,
                    jnp.asarray(j * block, jnp.int32), w, dims=dims,
                    mode=mode)
                outs.append(y)
            xs[i] = jnp.concatenate(outs, axis=0)
        del w
    out = []
    for x, r in zip(xs, rows):
        sel = x[jnp.asarray(r, jnp.int32)]
        out.append(np.asarray(_logits(sel, head, dims=dims, mode=mode)))
    return out


# -- the work model ----------------------------------------------------------

def dispatch(rows: int, seqs, logit_rows: int, dims: Dims, pk: dict
             ) -> work.Work:
    """One fused dispatch over `rows` real rows whose sequences are `seqs`
    (rows, summed keys, keys walked)."""
    w = work.Work(dispatches=1)
    for name in PROJS:
        k, n = dims.proj_shape(name)
        ops, t = work.gemm(rows, k, n, pk)
        w.int8_ops += ops * dims.n_layers
        w.roof("gemm", t * dims.n_layers)
    d, qd = dims.d_model, dims.n_heads * dims.head_dim
    q = (work.quantizer(rows, d, 1, pk) + work.quantizer(rows, qd, 1, pk)
         + work.quantizer(rows, d, 1, pk)
         + work.quantizer(rows, dims.d_ff, dims.block_size, pk))
    w.roof("quant", q * dims.n_layers)
    fl, t = work.attention(seqs, dims.n_heads, dims.n_kv_heads,
                           dims.head_dim, pk)
    w.bf16_flops += fl * dims.n_layers + 2.0 * d * dims.vocab * logit_rows
    w.roof("attn", t * dims.n_layers)
    return w


def step_work(step, dims: Dims, pk: dict, chunk: int) -> work.Work:
    """Work of one recorded engine step (see `driver.StepRecord`)."""
    total = work.Work()
    if step.decode_ctx:
        seqs = [(1, n, n) for n in step.decode_ctx]
        total.add(dispatch(len(step.decode_ctx), seqs, len(step.decode_ctx),
                           dims, pk))
    for start, end, completed in step.prefill:
        # one dispatch per chunk; each row attends to every key up to itself
        a = start
        while a < end:
            b = min(a + chunk, end)
            rows = b - a
            keys = rows * (a + b + 1) / 2.0
            last = b == end and completed
            total.add(dispatch(rows, [(rows, keys, b)], int(last), dims, pk))
            a = b
    return total
