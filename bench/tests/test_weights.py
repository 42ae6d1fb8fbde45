"""Weights from the seed: one layer made again equals the same layer of
the whole tree, so the reference can rebuild the model layer by layer;
the layers sit in one stack per kind of layer."""
import functools
import json
import os
import shutil
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, weights
from bench.archs import llama
from bench.reference import llama as ref_llama
from bench.tests import small


def _equal(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


def test_one_layer_again_equals_the_stacked_tree():
    c = small.cell("tl1-batch").config
    layout = llama.weight_layout(c)
    seed = 2**40 + 3
    tree = weights.make(layout, {"blocks": c["num_hidden_layers"]}, seed)
    top = weights.top(layout, seed)
    np.testing.assert_array_equal(np.asarray(top["embed"]), np.asarray(tree["embed"]))
    for i in range(c["num_hidden_layers"]):
        one = weights.layer(layout, seed, i)
        _equal(one, jax.tree.map(lambda a: a[i], tree["blocks"]))


def test_seeds_give_different_weights_of_the_stated_scale():
    c = small.cell("tl1-batch").config
    layout = llama.weight_layout(c)
    a = weights.make(layout, {"blocks": 2}, 1)["blocks"]["ffn"]["w_down"]["w"]
    b = weights.make(layout, {"blocks": 2}, 2)["blocks"]["ffn"]["w_down"]["w"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    std = float(np.std(np.asarray(a, np.float32)))
    assert abs(std - c["intermediate_size"] ** -0.5) < 0.1 * std


@functools.partial(jax.jit, static_argnames=("layout", "layers", "dtype"))
def _one_depth(key, layout, layers, dtype):
    """The generator as it was with one depth for every ``blocks/`` leaf."""
    flat = {}
    for j, (path, shape, init) in enumerate(layout):
        k = jax.random.fold_in(key, j)
        if path.startswith("blocks/"):
            ks = jax.vmap(lambda i: jax.random.fold_in(k, i))(jnp.arange(layers))
            flat[path] = jax.vmap(lambda kk: weights._leaf(kk, shape, init, dtype))(ks)
        else:
            flat[path] = weights._leaf(k, shape, init, dtype)
    return weights._nest(flat)


def test_the_default_stack_gives_the_tree_of_one_depth():
    c = small.cell("wtab-batch").config
    layout = llama.weight_layout(c)
    seed = 2**37 + 11
    L = c["num_hidden_layers"]
    want = _one_depth(weights.base_key(seed), weights._frozen(layout), L, jnp.bfloat16)
    got = weights.make(layout, spec.stacks(c), seed)
    assert spec.stacks(c) == {"blocks": L}
    assert jax.tree.structure(got) == jax.tree.structure(want)
    _equal(got, want)


def _two_kinds(c):
    """Granite's toy layout with one leading layer of another kind."""
    layout = llama.weight_layout(c)
    for path, v in list(layout.items()):
        if path.startswith("blocks/"):
            layout["dense_blocks/" + path[len("blocks/"):]] = v
    return layout


def test_two_stacks_have_their_depths_and_rebuild_layer_by_layer():
    c = small.cell("wtab-batch").config
    layout = _two_kinds(c)
    stacks = {"dense_blocks": 1, "blocks": 3}
    seed = 2**36 + 5
    tree = weights.make(layout, stacks, seed)
    for prefix, depth in stacks.items():
        leaves = jax.tree.leaves(tree[prefix])
        assert leaves and all(a.shape[0] == depth for a in leaves)
        for i in range(depth):
            _equal(weights.layer(layout, seed, i, prefix=prefix),
                   jax.tree.map(lambda a: a[i], tree[prefix]))
    top = weights.top(layout, seed, stacks)
    assert set(top) == {"embed", "ln_f"}
    _equal(top, {k: tree[k] for k in top})
    # the first layer of each stack draws apart from the other's
    assert not np.array_equal(np.asarray(tree["dense_blocks"]["attn"]["wq"]["w"][0]),
                              np.asarray(tree["blocks"]["attn"]["wq"]["w"][0]))


def _toy_arch(stacks):
    mod = types.ModuleType("bench.archs.toystack")
    for fn in ("program_config", "weight_layout", "linear_work"):
        setattr(mod, fn, getattr(llama, fn))
    mod.stacks = lambda c: stacks
    return mod


def _root_with(tmp_path, model_type):
    """A checkout's ``BENCHMARK.json``, configuration and traffic files, with
    the weight-table configuration under another ``model_type``."""
    root = tmp_path / "root"
    for rel in ("BENCHMARK.json", "bench/configs/granite_8b-wtab.json",
                "bench/traffic/batch.json"):
        os.makedirs(root / os.path.dirname(rel), exist_ok=True)
        shutil.copy(os.path.join(spec.ROOT, rel), root / rel)
    path = root / "bench/configs/granite_8b-wtab.json"
    c = json.loads(path.read_text())
    path.write_text(json.dumps(dict(c, model_type=model_type)))
    return str(root)


@pytest.mark.parametrize("stacks,why", [
    ({"dense_blocks": 1, "blocks": 4}, "do not make up"),
    ({"dense_blocks": 1, "blocks": 2}, "do not make up"),
    ({"blocks": 1, "blocks_moe": 3}, "starts"),
])
def test_load_cell_refuses_stacks_that_do_not_make_up_the_model(
        tmp_path, monkeypatch, stacks, why):
    monkeypatch.setitem(sys.modules, "bench.archs.toystack", _toy_arch(stacks))
    monkeypatch.setitem(sys.modules, "bench.reference.toystack", ref_llama)
    root = _root_with(tmp_path, "toystack")
    with pytest.raises(SystemExit, match=why):
        spec.load_cell("wtab-batch", root=root)


def test_load_cell_takes_stacks_that_make_up_the_model(tmp_path, monkeypatch):
    monkeypatch.setitem(sys.modules, "bench.archs.toystack",
                        _toy_arch({"dense_blocks": 1, "blocks": 3}))
    monkeypatch.setitem(sys.modules, "bench.reference.toystack", ref_llama)
    cell = spec.load_cell("wtab-batch", root=_root_with(tmp_path, "toystack"))
    assert spec.stacks(cell.config) == {"dense_blocks": 1, "blocks": 3}
