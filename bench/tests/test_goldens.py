"""The dense families against readings of the harness from before families
existed (``data/goldens.json``, taken at the small size of ``small.py``):
parameter shapes, initial weights, logits, the loss, and FLOP and cost
counts are what they were."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts, spec
from bench.reference import common
from bench.tests import small

GOLDENS = json.loads((Path(__file__).resolve().parent / "data" / "goldens.json")
                     .read_text())
NAMES = sorted(GOLDENS)


def _setup(name):
    cfg, _ = small.config(name)
    fam = spec.family(cfg["family"])
    return cfg, fam, common.init_params(cfg, fam.param_shapes(cfg),
                                        jax.random.PRNGKey(0))


@pytest.mark.parametrize("name", NAMES)
def test_param_shapes(name):
    cfg, fam, _ = _setup(name)
    shapes = jax.tree.map(list, fam.param_shapes(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    assert shapes == GOLDENS[name]["shapes"]


@pytest.mark.parametrize("name", NAMES)
def test_init_checksum(name):
    _, _, params = _setup(name)
    got = {n: [float(np.sum(np.asarray(x, np.float64))),
               float(np.sum(np.square(np.asarray(x, np.float64))))]
           for n, x in zip(common.leaf_names(params), jax.tree_util.tree_leaves(params))}
    assert got == GOLDENS[name]["init"]


@pytest.mark.parametrize("name", NAMES)
def test_logits_and_loss(name):
    cfg, fam, params = _setup(name)
    want = GOLDENS[name]
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, cfg["vocab_size"])
    logits = np.asarray(fam.forward(cfg, params, tokens), np.float64)
    np.testing.assert_allclose(logits[0, 0, :8], want["logits"]["first"], rtol=1e-6)
    np.testing.assert_allclose(logits[-1, -1, :8], want["logits"]["last"], rtol=1e-6)
    np.testing.assert_allclose(np.abs(logits).sum(), want["logits"]["abs_sum"], rtol=1e-6)
    np.testing.assert_allclose(logits.sum(), want["logits"]["sum"],
                               atol=1e-6 * want["logits"]["abs_sum"])
    nll = common.nll_sum(fam.forward, cfg, params, tokens, jnp.roll(tokens, -1, axis=1))
    np.testing.assert_allclose(float(nll), want["nll_sum"], rtol=1e-6)


@pytest.mark.parametrize("name", NAMES)
def test_flop_and_cost_counts(name):
    cfg, fam, _ = _setup(name)
    want = GOLDENS[name]["counts"]
    assert fam.matmul_params(cfg) == want["matmul_params"]
    assert fam.train_flops_per_token(cfg, 32) == want["train_flops_per_token"]
    assert fam.forward_flops(cfg, list(range(1, 41))) == want["forward_flops"]
    calls = [(1, 10), (16, 40), (3, 5)]
    assert list(counts.paged_attention_cost(cfg, calls)) == want["paged_attention_cost"]
    cost = fam.kernels["_paged_kernel"]
    assert list(cost(cfg, [], {"paged_calls": calls})) == want["paged_attention_cost"]
