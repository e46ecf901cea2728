"""Plain reference of a pre-norm decoder: the forward pass in float32
``jax.numpy`` at the highest matmul precision, with no kernel, cache,
paging or batching. It imports nothing of the program under test and
takes its weights from the benchmark's own generator.

The block, as the configuration files state it: RMSNorm, causal
grouped-query attention with interleaved rotary embeddings, RMSNorm,
an MLP (``relu2``: relu(x W_up)^2 W_down; ``silu`` gated:
(silu(x W_gate) * x W_up) W_down), each added back to the residual;
a final RMSNorm and logits against the tied embedding.

``control=True`` is the same arithmetic with every matmul operand
rounded to float8 (e4m3) with a scale per row or column along the
contraction: the step below the configuration's bfloat16. It decides
nothing in a run; it is what the comparison has to be able to fail.

``layout`` names every leaf of the weights this reference reads, with
its shape, dtype and scale; the harness draws them from the seed
(``harness.weights``) for the program and for this reference alike.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F8_MAX = 448.0   # largest finite float8_e4m3fn
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
# logits of about this spread, whatever the hidden size: the tied
# embedding's entries are drawn at LOGIT_STD / sqrt(hidden)
LOGIT_STD = 2.0
# norm gains are drawn about 1 so that a gain the program dropped or
# applied twice shows in the comparison
NORM_STD = 0.1


def layout(cfg: dict) -> dict[str, tuple[tuple[int, ...], object, str, float]]:
    """name -> (shape, dtype, kind, scale) of every leaf: pre-norm blocks
    with grouped-query attention and a plain or gated MLP, stacked over
    the layers, and tied input and output embeddings (the vocabulary
    padded to a multiple of 16). ``kind`` is ``normal`` (zero mean) or
    ``gain`` (mean 1)."""
    dt = DTYPES[cfg["torch_dtype"]]
    n, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, f, v = cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]
    v = -(-v // 16) * 16

    def mat(shape):
        return ((n, *shape), dt, "normal", 1.0 / math.sqrt(shape[0]))

    out = {
        "embed": ((v, d), dt, "normal", LOGIT_STD / math.sqrt(d)),
        "final_norm": ((d,), dt, "gain", NORM_STD),
        "blocks/dense/attn_norm": ((n, d), dt, "gain", NORM_STD),
        "blocks/dense/mlp_norm": ((n, d), dt, "gain", NORM_STD),
        "blocks/dense/attn/wq": mat((d, h * dh)),
        "blocks/dense/attn/wk": mat((d, hkv * dh)),
        "blocks/dense/attn/wv": mat((d, hkv * dh)),
        "blocks/dense/attn/wo": mat((h * dh, d)),
        "blocks/dense/mlp/w_up": mat((d, f)),
        "blocks/dense/mlp/w_down": mat((f, d)),
    }
    if cfg["gated_mlp"]:
        out["blocks/dense/mlp/w_gate"] = mat((d, f))
    return out


def _f8(x: jax.Array, axis: int) -> jax.Array:
    """Round ``x`` to float8 e4m3 with one scale per slice along
    ``axis`` (the contraction axis), and back to float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _dot(a, b, spec: str, ca: int, cb: int, control: bool):
    if control:
        a, b = _f8(a, ca), _f8(b, cb)
    return jnp.einsum(spec, a, b, precision="highest",
                      preferred_element_type=jnp.float32)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (T, heads, dh): pairs (2i, 2i+1) rotated by pos * theta^(-2i/dh)."""
    t, _, dh = x.shape
    freq = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _block(cfg: dict, control: bool, x, w):
    f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    t = x.shape[0]
    a = _norm(x, f32["attn_norm"], eps)
    q = _dot(a, f32["attn/wq"], "td,df->tf", 1, 0, control).reshape(t, h, dh)
    k = _dot(a, f32["attn/wk"], "td,df->tf", 1, 0, control)
    v = _dot(a, f32["attn/wv"], "td,df->tf", 1, 0, control)
    q = _rope(q, theta).reshape(t, hkv, h // hkv, dh)
    k = _rope(k.reshape(t, hkv, dh), theta)
    v = v.reshape(t, hkv, dh)
    s = _dot(q, k, "tkgd,skd->kgts", 3, 2, control) / jnp.sqrt(float(dh))
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _dot(p, v, "kgts,skd->tkgd", 3, 0, control).reshape(t, h * dh)
    x = x + _dot(o, f32["attn/wo"], "tf,fd->td", 1, 0, control)
    m = _norm(x, f32["mlp_norm"], eps)
    up = _dot(m, f32["mlp/w_up"], "td,df->tf", 1, 0, control)
    if cfg["gated_mlp"]:
        gate = _dot(m, f32["mlp/w_gate"], "td,df->tf", 1, 0, control)
        act = jax.nn.silu(gate) * up
    elif cfg["hidden_act"] == "relu2":
        act = jnp.square(jax.nn.relu(up))
    else:
        raise ValueError(f"no plain MLP with {cfg['hidden_act']!r}")
    return x + _dot(act, f32["mlp/w_down"], "tf,fd->td", 1, 0, control)


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _logits(w, tokens, positions, *, cfg_items, control):
    cfg = dict(cfg_items)
    emb = w["embed"].astype(jnp.float32)
    x = emb[tokens]
    blocks = {k[len("blocks/dense/"):]: v for k, v in w.items()
              if k.startswith("blocks/dense/")}
    x, _ = jax.lax.scan(
        lambda x, lw: (_block(cfg, control, x, lw), None), x, blocks)
    x = _norm(x[positions], w["final_norm"].astype(jnp.float32),
              cfg["rms_norm_eps"])
    logits = _dot(x, emb, "pd,vd->pv", 1, 1, control)
    return logits[:, :cfg["vocab_size"]]


SCALARS = ("num_attention_heads", "num_key_value_heads", "head_dim",
           "rms_norm_eps", "rope_theta", "gated_mlp", "hidden_act",
           "vocab_size")


def logits(weights: dict, cfg: dict, tokens, positions, *,
           control: bool = False) -> jax.Array:
    """Float32 logits (len(positions), vocab) of the causal forward over
    ``tokens`` (1-D, padded at the end as the caller likes), read at
    ``positions``."""
    items = tuple((k, cfg[k]) for k in SCALARS)
    return _logits(weights, jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(positions, jnp.int32), cfg_items=items,
                   control=control)
