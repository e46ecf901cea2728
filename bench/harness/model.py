"""A configuration file, the program's model config built from it, and
the weights the benchmark makes from the seed.

The weights are the benchmark's own: every leaf is drawn from the seed
by ``weights.make`` under a name of the layout that the configuration's
reference gives, and the same call gives the plain reference the same
numbers. The program gets them re-nested into its own parameter tree,
after a check that its tree has exactly these leaves, shapes and
dtypes.

A configuration's sizes map onto the program's ``ModelConfig`` through
``FIELDS``; an optional ``"program"`` object sets further fields by
their own names (an architecture's own sizes, say), each held to the
registry's published value unless its key is listed in ``reduced``.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp

from harness import weights

# configuration-file key -> the program's ModelConfig field
FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
}
ACTIVATIONS = {"relu2": "squared_relu", "silu": "silu"}
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def load_config(path: Path) -> dict:
    cfg = json.loads(Path(path).read_text())
    for key in ("program_arch", "torch_dtype", "hidden_act", "gated_mlp",
                *FIELDS):
        if key not in cfg:
            raise ValueError(f"{path}: configuration lacks {key!r}")
    return cfg


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file: its
    registry entry with every size set from the file and the Pallas
    kernels on. Raises if a width the file states differs from the
    registry's published one (only depth and vocabulary may be cut)."""
    from repro import configs

    base = configs.get_config(cfg["program_arch"])
    kw = {field: cfg[key] for key, field in FIELDS.items()}
    kw.update(
        activation=ACTIVATIONS[cfg["hidden_act"]],
        gated_mlp=bool(cfg["gated_mlp"]),
        dtype=DTYPES[cfg["torch_dtype"]],
        kv_cache_dtype=DTYPES[cfg.get("kv_cache_dtype", cfg["torch_dtype"])],
        use_pallas=True,
    )
    cut = set(cfg.get("reduced", {}))
    checked = {key: field for key, field in FIELDS.items()
               if key not in ("rope_theta", "rms_norm_eps")}
    extra = {field: tuple(v) if isinstance(v, list) else v
             for field, v in cfg.get("program", {}).items()}
    known = {f.name for f in dataclasses.fields(base)}
    for field, value in extra.items():
        if field not in known:
            raise ValueError(f"{cfg['name']}: 'program' sets {field!r}, "
                             "which the program's ModelConfig lacks")
        checked[field] = field
        kw[field] = value
    for key, field in checked.items():
        if key not in cut and getattr(base, field) != kw[field]:
            raise ValueError(
                f"{cfg['name']}: {key} {kw[field]} differs from the "
                f"program's {cfg['program_arch']} ({getattr(base, field)})"
                " and is not listed in 'reduced'")
    return dataclasses.replace(base, **kw)


def _path_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def program_params(cfg: dict, api, pcfg, seed: int, minfo, shardings=None):
    """The benchmark's weights for ``seed``, nested as the program's
    parameter tree. The leaves must match the program's specs exactly.
    ``shardings``, a tree like the parameters' (the program's own
    tensor-parallel placement), places each leaf as it is drawn."""
    specs = api.param_specs(pcfg, minfo)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: hasattr(x, "pspec"))
    layout = weights.layout(cfg)
    want = {_path_name(p): (tuple(s.shape), jnp.dtype(s.dtype))
            for p, s in flat}
    have = {k: (v[0], jnp.dtype(v[1])) for k, v in layout.items()}
    if want != have:
        diff = sorted(set(want.items()) ^ set(have.items()))
        raise ValueError(f"the program's parameters differ from the "
                         f"benchmark's layout: {diff[:6]}")
    if shardings is not None:
        shardings = {_path_name(p): s for p, s in
                     jax.tree_util.tree_flatten_with_path(shardings)[0]}
    made = weights.make(cfg, seed, shardings)
    return jax.tree_util.tree_unflatten(
        treedef, [made[_path_name(p)] for p, _ in flat])
