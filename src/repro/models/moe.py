"""Mixture-of-Experts with expert parallelism (deepseek-v3, llama4-scout).

Routing is token-choice top-k with per-expert capacity (gather-based):

  * the router (softmax + top-k) is a *flexible* op — it lives in router
    space exactly like an activation lives in MLP space, and it is the
    fastest-changing part of MoE designs (aux-loss-free biasing, sigmoid
    routers, ...). The expert MLPs are *static* primitives.
  * EP: experts are sharded over the "model" axis and FSDP'd over
    ("pod","data"); tokens are sharded over ("pod","data") and replicated
    over "model". Inside a ``shard_map`` island each model-rank:
      1. all-gathers its experts' weights over the FSDP axes (ZeRO-3),
      2. scores all local tokens for its E_local experts,
      3. picks top-C tokens per expert (capacity drop, by router weight),
      4. gathers/computes/scatter-adds,
    and the partial outputs are psum'd over "model". No all-to-all — at
    these expert counts the replicated-token EP pattern keeps the only
    cross-chip traffic at psum(B·S·D), which the roofline tracks.
  * shared experts (deepseek-v3) run dense, TP-sharded over "model".

The HOST (single-device) path runs the same algorithm without collectives.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ModelConfig
from repro.models.layers import MeshInfo, ParamSpec, _maybe
from repro.parallel import tp

Array = jax.Array


def moe_param_specs(cfg: ModelConfig, m: MeshInfo) -> dict:
    d, f, e, dt = cfg.d_model, cfg.moe_d_ff, cfg.num_experts, cfg.dtype
    fsdp = tuple(m.fsdp) or None
    specs = {
        "router": ParamSpec((d, e), dt, _maybe(m, fsdp, None)),
        # experts: E over "model" (EP), D over FSDP (ZeRO-3)
        "w_gate": ParamSpec((e, d, f), dt, _maybe(m, "model", fsdp, None)),
        "w_up": ParamSpec((e, d, f), dt, _maybe(m, "model", fsdp, None)),
        "w_down": ParamSpec((e, f, d), dt, _maybe(m, "model", None, fsdp)),
    }
    if cfg.num_shared_experts:
        fs = cfg.moe_d_ff * cfg.num_shared_experts
        specs["shared"] = {
            "w_gate": ParamSpec((d, fs), dt, _maybe(m, fsdp, "model")),
            "w_up": ParamSpec((d, fs), dt, _maybe(m, fsdp, "model")),
            "w_down": ParamSpec((fs, d), dt, _maybe(m, "model", fsdp)),
        }
    return specs


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    c = math.ceil(tokens * cfg.experts_per_token / cfg.num_experts
                  * cfg.capacity_factor)
    return min(tokens, max(8, (c + 7) // 8 * 8))


def _expert_mlp(x: Array, wg: Array, wu: Array, wd: Array, act) -> Array:
    """(C, D) tokens through one expert; static primitives + flexible act."""
    g = act(jnp.dot(x, wg, preferred_element_type=jnp.float32))
    u = jnp.dot(x, wu, preferred_element_type=jnp.float32)
    return jnp.dot((g * u).astype(x.dtype), wd,
                   preferred_element_type=jnp.float32).astype(x.dtype)


def _route(x: Array, router_w: Array, cfg: ModelConfig) -> tuple[Array, Array]:
    """Flexible op: router softmax + top-k. x (T, D) -> weights/ids (T, k)."""
    logits = jnp.dot(x, router_w, preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, cfg.experts_per_token)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return weights.astype(jnp.float32), ids


def _local_expert_pass(x: Array, weights: Array, ids: Array,
                       w_gate: Array, w_up: Array, w_down: Array,
                       e_offset: Array | int, cfg: ModelConfig, act) -> Array:
    """Run E_local experts over T local tokens. Returns (T, D) partial sum."""
    t = x.shape[0]
    e_local = w_gate.shape[0]
    cap = _capacity(t, cfg)

    def one_expert(j, wg, wu, wd):
        gid = e_offset + j
        score = jnp.sum(jnp.where(ids == gid, weights, 0.0), axis=-1)  # (T,)
        top_w, top_idx = jax.lax.top_k(score, cap)                     # capacity
        xe = jnp.take(x, top_idx, axis=0)                              # (C, D)
        ye = _expert_mlp(xe, wg, wu, wd, act)
        ye = ye * top_w[:, None].astype(ye.dtype)
        return jnp.zeros((t, x.shape[1]), ye.dtype).at[top_idx].add(ye)

    parts = jax.vmap(one_expert, in_axes=(0, 0, 0, 0))(
        jnp.arange(e_local), w_gate, w_up, w_down
    )
    return jnp.sum(parts, axis=0)


def moe(
    params: dict,
    cfg: ModelConfig,
    x: Array,                     # (B, S, D)
    *,
    table,
    minfo: MeshInfo,
    mesh: Mesh | None = None,
) -> Array:
    act = table.lookup(cfg.activation)
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)

    use_shard_map = (
        mesh is not None
        and "model" in minfo.axis_names
        and cfg.moe_dispatch == "shard_map"
    )
    if tp.active() is not None:
        # Already inside a manual shard_map (TP serving): the router is
        # replicated so routing decisions are GLOBAL expert ids, while
        # the expert stack is sharded over the model axis — run the
        # local experts at this shard's id offset and leave y a PARTIAL
        # sum. The shared-expert slice below adds its own partial and
        # ONE merged psum at the end reassembles the layer output.
        weights, ids = _route(x2, params["router"], cfg)
        e_local = params["w_gate"].shape[0]
        y = _local_expert_pass(
            x2, weights, ids, params["w_gate"], params["w_up"],
            params["w_down"], tp.shard_offset(e_local), cfg, act,
        )
    elif not use_shard_map:
        weights, ids = _route(x2, params["router"], cfg)
        y = _local_expert_pass(
            x2, weights, ids, params["w_gate"], params["w_up"],
            params["w_down"], 0, cfg, act,
        )
    else:
        fsdp = tuple(minfo.fsdp)
        tok_spec = _maybe(minfo, fsdp or None, None)       # (T, D)
        ew_spec = _maybe(minfo, "model", fsdp or None, None)
        ed_spec = _maybe(minfo, "model", None, fsdp or None)
        r_spec = _maybe(minfo, fsdp or None, None)

        def shard_fn(x_l, wr_l, wg_l, wu_l, wd_l):
            # ZeRO-3 gather of this rank's expert weights over FSDP axes.
            if fsdp:
                wr_l = jax.lax.all_gather(wr_l, fsdp, axis=0, tiled=True)
                wg_l = jax.lax.all_gather(wg_l, fsdp, axis=1, tiled=True)
                wu_l = jax.lax.all_gather(wu_l, fsdp, axis=1, tiled=True)
                wd_l = jax.lax.all_gather(wd_l, fsdp, axis=2, tiled=True)
            weights, ids = _route(x_l, wr_l, cfg)
            e_local = wg_l.shape[0]
            e_offset = jax.lax.axis_index("model") * e_local
            y_l = _local_expert_pass(
                x_l, weights, ids, wg_l, wu_l, wd_l, e_offset, cfg, act,
            )
            return jax.lax.psum(y_l, "model")

        y = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(tok_spec, r_spec, ew_spec, ew_spec, ed_spec),
            out_specs=tok_spec,
            check_vma=False,
        )(x2, params["router"], params["w_gate"], params["w_up"],
          params["w_down"])

    if cfg.num_shared_experts:
        sh = params["shared"]
        g = act(jnp.dot(x2, sh["w_gate"], preferred_element_type=jnp.float32))
        u = jnp.dot(x2, sh["w_up"], preferred_element_type=jnp.float32)
        y = y + jnp.dot((g * u).astype(x2.dtype), sh["w_down"],
                        preferred_element_type=jnp.float32).astype(y.dtype)

    return tp.psum_partial(y).reshape(b, s, d).astype(x.dtype)
