"""The benchmark's served tree has the program's layout, and the program
serving it agrees with the plain reference on the CPU."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from families import m3vit, m3vit_weights
from families import m3vit_reference as ref

HERE = os.path.dirname(__file__)


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(HERE, "tiny.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tree(spec):
    return m3vit_weights.make_tree(spec, 2**31 + 5)


def test_tree_has_the_layout_ptq_materializes(spec, tree):
    import repro.models as M
    from repro.configs import get_shape
    from repro.core.quant.ptq import calibrate_model, ptq_model

    cfg = m3vit.program_config(spec)
    key = jax.random.PRNGKey(0)
    params = M.init_model_params(cfg, key)
    shape = get_shape("train_4k").replace(seq_len=24, global_batch=2)
    taps = calibrate_model(cfg, params, [M.synth_batch(cfg, shape, key)])
    ptq = ptq_model(cfg, params, taps, materialize="int8")
    layout = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    assert layout(tree) == layout(ptq)


def test_seed_makes_the_tree(spec, tree):
    again = m3vit_weights.make_tree(spec, 2**31 + 5)
    other = m3vit_weights.make_tree(spec, 6)
    same = jax.tree.map(lambda a, b: bool(jnp.all(a == b)), tree, again)
    assert all(jax.tree.leaves(same))
    assert not bool(jnp.all(tree["head"] == other["head"]))


def test_scales_come_from_calibration(spec, tree):
    for kind in ("pairs_dense", "pairs_moe"):
        lp = tree[kind]
        assert bool(jnp.all(lp["ln1"]["a_scale"] > 0))
        np.testing.assert_array_equal(lp["attn"]["wq_as"],
                                      lp["ln1"]["a_scale"])
        assert bool(jnp.all(lp["attn"]["wo_a_scale"] > 0))
    moe = tree["pairs_moe"]["moe"]
    np.testing.assert_array_equal(moe["gate_as"], moe["wi_as"])
    np.testing.assert_array_equal(tree["head_as"],
                                  tree["final_norm"]["a_scale"])


def test_program_matches_reference_on_cpu(spec, tree):
    """The program's jnp path (REPRO_PALLAS=ref) and the reference compute
    the same integers; only float summation order may differ."""
    from repro.models import vit

    x = jnp.asarray(m3vit.make_pool(spec, 3, n=8))
    got = vit.forward(tree, m3vit.program_config(spec), x)[0]
    want = ref.logits(tree, spec, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4 * float(jnp.std(want)))
