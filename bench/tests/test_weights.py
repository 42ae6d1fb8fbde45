"""Weights from the seed: one layer made again equals the same layer of
the whole tree, so the reference can rebuild the model layer by layer."""
import jax
import numpy as np

from bench import weights
from bench.archs import llama
from bench.tests import small


def test_one_layer_again_equals_the_stacked_tree():
    c = small.cell("tl1-batch").config
    layout = llama.weight_layout(c)
    seed = 2**40 + 3
    tree = weights.make(layout, c["num_hidden_layers"], seed)
    top = weights.top(layout, seed)
    np.testing.assert_array_equal(np.asarray(top["embed"]), np.asarray(tree["embed"]))
    for i in range(c["num_hidden_layers"]):
        one = weights.layer(layout, seed, i)
        want = jax.tree.map(lambda a: a[i], tree["blocks"])
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), one, want)


def test_seeds_give_different_weights_of_the_stated_scale():
    c = small.cell("tl1-batch").config
    layout = llama.weight_layout(c)
    a = weights.make(layout, 2, 1)["blocks"]["ffn"]["w_down"]["w"]
    b = weights.make(layout, 2, 2)["blocks"]["ffn"]["w_down"]["w"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    std = float(np.std(np.asarray(a, np.float32)))
    assert abs(std - c["intermediate_size"] ** -0.5) < 0.1 * std
