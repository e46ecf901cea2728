"""The harness on more than one chip, and the weights it draws.

* The draw of the two one-chip cells' configurations is pinned leaf by
  leaf: a change to the layout or to the draw shows here before it
  moves a cell's numbers.
* A configuration names its weights' layout through its reference, so a
  new architecture brings a reference module and a configuration file
  and edits no file of the harness.
* On four virtual CPU devices, at a smoke size: the weights drawn into
  a placement equal the one-device draw bit for bit, the reference over
  sharded weights gives the one-device logits, and ``cellrun.build`` on
  a (1, 4) mesh serves the same greedy tokens as without one.
"""

import hashlib
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from harness import weights

BENCH = Path(__file__).resolve().parent
# the cut of each one-chip configuration whose draw is pinned: 2 layers,
# 1,000 ids and narrower widths (two whole-width layers take 9.6 GB of
# host memory to draw), every other key as the configuration states it
CUTS = {
    "nemotron-4-15b-8L": dict(hidden_size=768, num_attention_heads=6,
                              num_key_value_heads=1,
                              intermediate_size=3072),
    "deepseek-7b-8L": dict(hidden_size=512, num_attention_heads=4,
                           num_key_value_heads=4, intermediate_size=1376),
}
SEED = 2**33 + 7
# sha256 (first 16 hex digits) of each leaf's bytes, as drawn before the
# harness learnt to place weights over several chips
PINNED = {
    "nemotron-4-15b-8L": {
        "blocks/dense/attn/wk": "02bbdee0a81cc7c9",
        "blocks/dense/attn/wo": "03b0bef3749b5c7a",
        "blocks/dense/attn/wq": "1229bf6ea0fdb080",
        "blocks/dense/attn/wv": "ff2ca51adaf815a5",
        "blocks/dense/attn_norm": "6c86c53eb5307285",
        "blocks/dense/mlp/w_down": "d8edc3a80dca3e4b",
        "blocks/dense/mlp/w_up": "8c51dd7336407b97",
        "blocks/dense/mlp_norm": "2bdaf076a66344ee",
        "embed": "f8fa9072fbee912c",
        "final_norm": "d409876a166da09a",
    },
    "deepseek-7b-8L": {
        "blocks/dense/attn/wk": "9cdef5ea134e4f72",
        "blocks/dense/attn/wo": "032f26238d189718",
        "blocks/dense/attn/wq": "34e4aa374bed2e52",
        "blocks/dense/attn/wv": "40cdea215690c435",
        "blocks/dense/attn_norm": "493d331562d360e5",
        "blocks/dense/mlp/w_down": "9603d9f7497068aa",
        "blocks/dense/mlp/w_gate": "f27c6b9959e601fe",
        "blocks/dense/mlp/w_up": "2a403717ac7d2186",
        "blocks/dense/mlp_norm": "bc946da98cba6f5e",
        "embed": "c3b7133664f8aae4",
        "final_norm": "386ea231ef597b1b",
    },
}
# the same digest of each configuration's whole layout, at its own sizes:
# every leaf's name, shape, dtype, kind and scale
PINNED_LAYOUT = {"nemotron-4-15b-8L": "57c536ab6da9a882",
                 "deepseek-7b-8L": "9c4474c705ee07e0"}


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _digest(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_one_chip_draws_are_pinned(name):
    cfg = _config(name)
    lay = weights.layout(cfg)
    rows = sorted((k, v[0], str(np.dtype(v[1])), v[2], v[3])
                  for k, v in lay.items())
    assert _digest(repr(rows).encode()) == PINNED_LAYOUT[name]
    cfg.update(num_hidden_layers=2, vocab_size=1000, **CUTS[name])
    drawn = {k: _digest(np.asarray(v).view(np.uint8).tobytes())
             for k, v in weights.make(cfg, SEED).items()}
    assert drawn == PINNED[name]


TOY_REFERENCE = '''
import jax.numpy as jnp

def layout(cfg):
    n, s = cfg["num_hidden_layers"], cfg["state_size"]
    return {"embed": ((cfg["vocab_size"], s), jnp.float32, "normal", 0.5),
            "cells/decay": ((n, s), jnp.float32, "gain", 0.01)}

def logits(weights, cfg, tokens, positions, *, control=False):
    return weights["embed"][tokens][positions]
'''


def test_a_new_architecture_names_its_layout_by_files_alone(tmp_path,
                                                           monkeypatch):
    # a bench directory that holds one more reference module, and
    # nothing else new
    bench = tmp_path / "bench"
    (bench / "references").mkdir(parents=True)
    (bench / "references" / "toy_recurrent.py").write_text(TOY_REFERENCE)
    manifest = weights.manifest
    found = manifest.reference
    monkeypatch.setattr(manifest, "reference",
                        lambda name: found(name, bench))
    cfg = {"name": "toy", "reference": "toy_recurrent",
           "num_hidden_layers": 3, "state_size": 16, "vocab_size": 40}
    lay = weights.layout(cfg)
    assert {k: v[0] for k, v in lay.items()} == {
        "embed": (40, 16), "cells/decay": (3, 16)}
    made = weights.make(cfg, 5)
    assert made["cells/decay"].shape == (3, 16)
    assert abs(float(made["cells/decay"].mean()) - 1.0) < 0.01
    # the dense decoder's layout is its reference's
    monkeypatch.undo()
    dense = _config("nemotron-4-15b-8L")
    assert weights.layout(dense) == manifest.reference(
        "decoder").layout(dense)


def test_program_object_sets_further_fields_held_to_the_registry():
    from harness import model

    cfg = _config("nemotron-4-15b-8L")
    base = model.program_config(cfg)
    cfg["program"] = {"capacity_factor": base.capacity_factor}
    assert model.program_config(cfg).capacity_factor == base.capacity_factor
    cfg["program"] = {"capacity_factor": base.capacity_factor * 2}
    with pytest.raises(ValueError, match="capacity_factor"):
        model.program_config(cfg)
    cfg["reduced"] = {**cfg["reduced"], "capacity_factor": "twice"}
    assert model.program_config(cfg).capacity_factor == base.capacity_factor * 2
    cfg["program"] = {"no_such_field": 1}
    with pytest.raises(ValueError, match="no_such_field"):
        model.program_config(cfg)


MESH_SCRIPT = textwrap.dedent('''
    import json, sys
    import jax, numpy as np
    from jax.sharding import Mesh
    from harness import cellrun, manifest, model, weights

    devs = jax.devices()
    assert len(devs) == 4, devs
    cell = manifest.Cell(**json.loads(sys.argv[1]))
    cfg = cell.config
    one = weights.make(cfg, 2**40 + 3)

    # the benchmark's own placement, for the reference
    mesh = Mesh(np.asarray(devs), ("chips",))
    lay = weights.layout(cfg)
    spread = weights.make(cfg, 2**40 + 3, weights.spread(lay, mesh, "chips"))
    for k in one:
        assert len(spread[k].sharding.device_set) == 4 or \\
            spread[k].ndim == 0, k
        np.testing.assert_array_equal(np.asarray(spread[k]),
                                      np.asarray(one[k]), err_msg=k)

    # the reference over sharded weights
    ref = manifest.reference(cfg["reference"])
    toks = np.random.RandomState(0).randint(0, cfg["vocab_size"], 64)
    pos = np.arange(20, 52, dtype=np.int32)
    a = np.asarray(ref.logits(one, cfg, toks, pos))
    b = np.asarray(ref.logits(spread, cfg, toks, pos))
    np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-5 * np.abs(a).max())
    print("reference sharded max diff", float(np.abs(a - b).max()))

    # the program's own tensor-parallel placement
    cellrun.capacity = lambda dev: 2**26
    logs = []
    srv4, _ = cellrun.build(cell, 2**40 + 3, devs, logs.append)
    flat = jax.tree_util.tree_flatten_with_path(srv4.params)[0]
    for path, x in flat:
        name = model._path_name(path)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(one[name]),
                                      err_msg=name)
    held = cellrun.device_bytes(srv4.params, devs)
    total = sum(x.nbytes for x in jax.tree.leaves(srv4.params))
    assert max(held) < total / 2, (held, total)
    srv1, _ = cellrun.build(cell, 2**40 + 3, devs[:1], logs.append)
    assert srv4.tp is not None and srv4.tp.size == 4 and srv1.tp is None
    rng = np.random.RandomState(5)
    reqs = [(rng.randint(0, cfg["vocab_size"], rng.randint(5, 30)),
             int(rng.randint(3, 9))) for _ in range(6)]
    out = []
    for srv in (srv4, srv1):
        for p, g in reqs:
            srv.submit(p, g)
        out.append({r.rid: np.asarray(r.tokens).tolist() for r in srv.run()})
    assert out[0] == out[1], out
    print("mesh build serves the meshless tokens", len(out[0]))
    print("\\n".join(logs))
''')


def test_four_devices_draw_refer_and_serve_alike(tmp_path):
    from test_bench_faults import TINY, tiny_cell

    cell = tiny_cell(6.0)
    cell.chips = 4
    cell.config.update(num_attention_heads=8, num_key_value_heads=4,
                       head_dim=32, intermediate_size=512)
    cell.config["reduced"] = {k: "smoke" for k in TINY}
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(BENCH), str(BENCH.parent / "src")]))
    args = {f: getattr(cell, f) for f in
            ("name", "chips", "config", "traffic", "limits", "end_to_end",
             "per_layer")}
    res = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT, json.dumps(args)],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "mesh build serves the meshless tokens 6" in res.stdout
