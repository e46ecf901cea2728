"""MLP blocks — the paper's canonical static→flexible→static pattern.

``gated`` (SwiGLU-family): y = (f(x@Wg) ⊙ (x@Wu)) @ Wd
``plain`` (nemotron squared-relu, rwkv channel-mix): y = f(x@W1) @ W2

The activation is a function-table key — swapping it (the paper's "new
activation function" scenario) touches no model or kernel code. In SIDEBAR
mode with ``cfg.use_pallas`` the plain MLP runs through the fused
``kernels.sidebar_mlp`` (VMEM-resident intermediate).
"""

from __future__ import annotations

import jax

from repro.configs.base import ModelConfig
from repro.core.function_table import DEFAULT_TABLE, FunctionTable
from repro.kernels import ops as kops
from repro.models.layers import MeshInfo, ParamSpec, _maybe, linear
from repro.parallel import tp

Array = jax.Array


def mlp_param_specs(cfg: ModelConfig, m: MeshInfo, d_ff: int | None = None) -> dict:
    d, f, dt = cfg.d_model, d_ff or cfg.d_ff, cfg.dtype
    fsdp = tuple(m.fsdp) or None
    specs = {
        "w_up": ParamSpec((d, f), dt, _maybe(m, fsdp, "model")),
        "w_down": ParamSpec((f, d), dt, _maybe(m, "model", fsdp)),
    }
    if cfg.gated_mlp:
        specs["w_gate"] = ParamSpec((d, f), dt, _maybe(m, fsdp, "model"))
    return specs


def mlp(
    params: dict,
    cfg: ModelConfig,
    x: Array,
    *,
    table: FunctionTable = DEFAULT_TABLE,
    activation: str | None = None,
) -> Array:
    """x (..., D) -> (..., D).

    The sidebar kernels take 2-D operands; higher-rank activations (the
    serving path is (B, S, D)) flatten their leading dims into the row
    axis — rows are independent for every op here, so the fused kernels
    serve decode/prefill shapes too (PR 3: before this, serving never
    reached the kernels and per-layer plans had nothing to dispatch to).

    Under ambient TP w_up/w_gate are column-parallel and w_down is
    row-parallel, so every exit below returns a PARTIAL down-projection
    over the local d_ff shard — psum'd on the model axis (identity
    outside TP). The sidebar kernels run per-shard unmodified: they only
    ever see the local (d, f_local)/(f_local, d) weight slices.
    """
    act_name = activation or cfg.activation
    act = table.lookup(act_name)
    d = x.shape[-1]
    if cfg.use_pallas and x.ndim >= 2:
        # the ops decide kernel vs jnp fallback from the shapes, and
        # record the choice either way (kops.record_dispatches)
        x2 = x.reshape(-1, d)
        if cfg.gated_mlp:
            y = kops.sidebar_gated_mlp(
                x2, params["w_gate"], params["w_up"], params["w_down"],
                act_name, table=table, interpret=kops.interpret_kernels(),
            )
        else:
            y = kops.sidebar_mlp(
                x2, params["w_up"], params["w_down"], act_name,
                table=table, interpret=kops.interpret_kernels(),
            )
        return tp.psum_partial(y.reshape(x.shape))
    if cfg.gated_mlp:
        g = act(linear(x, params["w_gate"]))          # flexible (VPU)
        u = linear(x, params["w_up"])                 # static  (MXU)
        return tp.psum_partial(
            linear((g * u).astype(x.dtype), params["w_down"]))
    h = act(linear(x, params["w_up"]))
    return tp.psum_partial(linear(h.astype(x.dtype), params["w_down"]))
