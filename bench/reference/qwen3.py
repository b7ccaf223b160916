"""Plain float32 reference of the Qwen3 decoder, and the seeded weights.

The reference follows Hugging Face's ``Qwen3ForCausalLM`` (modeling_qwen3):
RMSNorm before attention and MLP, per-head RMSNorm of queries and keys
before rotary embedding (rotate-half convention), grouped-query attention
scaled by ``head_dim ** -0.5``, SwiGLU MLP, final RMSNorm and an output
head tied to the embedding.  One departure: the value/output head size
``v_head_dim`` may differ from the query/key ``head_dim``, which is what a
structurally pruned model has (SPA prunes the value channels of a head
apart from its rotary query/key channels).  Every product runs in float32
at ``Precision.HIGHEST``; nothing here is batched, paged or cached.

``make_params`` draws the weights that both the system under test and this
reference are given, in one jitted call on the device, in the served
dtype.  The tree uses the layout the serving program reads.  This module
imports nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512            # query rows per attention block of the reference
NORM_NOISE = 0.1         # norm scales are 1 + N(0, NORM_NOISE^2)


def dims(conf: dict) -> dict:
    """The widths of ``conf`` (a configuration file's dict)."""
    hd = conf["head_dim"]
    return {"L": conf["num_hidden_layers"], "d": conf["hidden_size"],
            "H": conf["num_attention_heads"],
            "KH": conf["num_key_value_heads"], "hd": hd,
            "vhd": conf.get("v_head_dim", hd),
            "F": conf["intermediate_size"], "V": conf["vocab_size"],
            "eps": float(conf["rms_norm_eps"]),
            "theta": float(conf["rope_theta"])}


def program_arch(conf: dict) -> dict:
    """Keyword arguments of the serving program's architecture record."""
    d = dims(conf)
    if not conf["tie_word_embeddings"]:
        raise ValueError("the reference ties the output head to the embedding")
    return {"name": conf["name"], "family": "dense", "num_layers": d["L"],
            "d_model": d["d"], "n_heads": d["H"], "n_kv_heads": d["KH"],
            "head_dim": d["hd"], "v_head_dim": d["vhd"], "d_ff": d["F"],
            "vocab_size": d["V"], "qk_norm": True, "rope_theta": d["theta"],
            "norm_eps": d["eps"], "tie_embeddings": True,
            "dtype": conf["torch_dtype"]}


def _shapes(d: dict) -> dict:
    L, m, H, KH, hd, vhd, F, V = (d[k] for k in
                                  ("L", "d", "H", "KH", "hd", "vhd", "F", "V"))
    return {"tok_embed": (V, m),
            "layers": {"ln1": (L, m),
                       "attn": {"wq": (L, m, H, hd), "wk": (L, m, KH, hd),
                                "wv": (L, m, KH, vhd), "wo": (L, H, vhd, m),
                                "q_norm": (L, hd), "k_norm": (L, hd)},
                       "ln2": (L, m),
                       "mlp": {"w_gate": (L, m, F), "w_up": (L, m, F),
                               "w_down": (L, F, m)}},
            "final_norm": (m,)}


def _std(path: str, shape: tuple) -> float | None:
    """Standard deviation of a matrix leaf; None for a norm scale."""
    name = path.rsplit("/", 1)[-1]
    if name == "tok_embed":
        return 0.02
    if name in ("wq", "wk", "wv", "w_gate", "w_up"):
        return shape[1] ** -0.5
    if name == "wo":
        return (shape[1] * shape[2]) ** -0.5
    if name == "w_down":
        return shape[1] ** -0.5
    return None


def _flat(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _flat(tree[k], f"{prefix}/{k}" if prefix else k)
    else:
        yield prefix, tree


def _unflat(items: dict) -> dict:
    out: dict = {}
    for path, v in items.items():
        node = out
        *heads, last = path.split("/")
        for h in heads:
            node = node.setdefault(h, {})
        node[last] = v
    return out


def param_shapes(conf: dict) -> dict:
    return _shapes(dims(conf))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole number up to 64 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init(key, leaves: tuple, dtype: str):
    keys = jax.random.split(key, len(leaves))
    out = {}
    for k, (path, shape) in zip(keys, leaves):
        std = _std(path, shape)
        x = jax.random.normal(k, shape, jnp.float32)
        x = x * std if std is not None else 1.0 + NORM_NOISE * x
        out[path] = x.astype(dtype)
    return out


def make_params(conf: dict, seed: int) -> dict:
    """All weights from ``seed`` in one jitted call, in the served dtype."""
    leaves = tuple(_flat(param_shapes(conf)))
    return _unflat(_init(seed_key(seed), leaves, conf["torch_dtype"]))


# ---------------------------------------------------------------------------
# the reference forward
# ---------------------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _quant_int8(x, axis):
    """Symmetric int8 rounding with one scale per slice along ``axis``
    (the control's arithmetic); returns the dequantized float32 values."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _linear(x, w, quant: bool):
    """x (S, K) @ w (K, N) in float32; with ``quant`` both operands are
    first rounded to int8, x per row and w per output column."""
    if quant:
        x, w = _quant_int8(x, -1), _quant_int8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rope(x, cos, sin):
    """Rotate-half rotary embedding; x (S, heads, hd), cos/sin (S, hd)."""
    h = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., h:], x[..., :h]], -1)
    return x * cos[:, None] + rot * sin[:, None]


def rope_tables(positions: np.ndarray, hd: int, theta: float):
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = positions.astype(np.float64)[:, None] * inv[None]
    ang = np.concatenate([ang, ang], -1)
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


@functools.partial(jax.jit, static_argnames=("d", "quant"))
def _layer(x, layers, i, cos, sin, *, d, quant):
    """Decoder layer ``i`` of the stacked ``layers`` on x (S, d) f32."""
    lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False).astype(jnp.float32), layers)
    dd = dict(d)
    S, m = x.shape
    H, KH, hd, vhd, eps = dd["H"], dd["KH"], dd["hd"], dd["vhd"], dd["eps"]
    at = lp["attn"]
    h = _rms(x, lp["ln1"], eps)
    q = _linear(h, at["wq"].reshape(m, H * hd), quant).reshape(S, H, hd)
    k = _linear(h, at["wk"].reshape(m, KH * hd), quant).reshape(S, KH, hd)
    v = _linear(h, at["wv"].reshape(m, KH * vhd), quant).reshape(S, KH, vhd)
    q = _rope(_rms(q, at["q_norm"], eps), cos, sin)
    k = _rope(_rms(k, at["k_norm"], eps), cos, sin)
    G = H // KH
    q = q.reshape(S, KH, G, hd) * hd ** -0.5
    kpos = jnp.arange(S)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HIGHEST)
        qpos = start + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, jnp.arange(0, S, Q_BLOCK))      # (S/QB, QB, ...)
    o = o.reshape(S, H * vhd)
    x = x + _linear(o, at["wo"].reshape(H * vhd, m), quant)
    h = _rms(x, lp["ln2"], eps)
    mp = lp["mlp"]
    g = _linear(h, mp["w_gate"], quant)
    u = _linear(h, mp["w_up"], quant)
    return x + _linear(jax.nn.silu(g) * u, mp["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, final_norm, embed, rows, *, eps, quant):
    h = _rms(x[rows], final_norm.astype(jnp.float32), eps)
    return _linear(h, embed.astype(jnp.float32).T, quant)


def logits_at(params: dict, conf: dict, tokens, rows, quant: bool = False
              ) -> np.ndarray:
    """Logits (len(rows), V) float32 at positions ``rows`` of one sequence
    ``tokens`` (a whole prompt and its continuation), computed layer by
    layer.  The sequence is padded at its end to a power of two of at
    least ``Q_BLOCK``, and the rows to one of at least 64, so that few
    shapes compile; causal masking keeps the padding out of every real
    position.  ``quant`` gives the int8 control."""
    d = dims(conf)
    S, n = len(tokens), len(rows)
    Sp = max(Q_BLOCK, 1 << (S - 1).bit_length())
    rp = np.zeros(max(64, 1 << (n - 1).bit_length()), np.int32)
    rp[:n] = rows
    toks = np.zeros(Sp, np.int32)
    toks[:S] = tokens
    cos, sin = rope_tables(np.arange(Sp), d["hd"], d["theta"])
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["tok_embed"], jnp.asarray(toks), axis=0
                     ).astype(jnp.float32)
        key = tuple(sorted(d.items()))
        for i in range(d["L"]):
            x = _layer(x, params["layers"], i, cos, sin, d=key, quant=quant)
        out = _head(x, params["final_norm"], params["tok_embed"],
                    jnp.asarray(rp), eps=d["eps"], quant=quant)
    return np.asarray(out)[:n]
