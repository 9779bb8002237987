"""The plain references against the program at its reduced sizes (float32
on the CPU, so the two agree to rounding)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correct, spec, traffic
from bench.reference import adam, common
from bench.tests import small


def _init(cfg, key):
    return common.init_params(cfg, spec.family(cfg["family"]).param_shapes(cfg), key)


@pytest.mark.parametrize("name", ["gpt_small", "smollm_135m"])
def test_forward_matches_program(name):
    from repro.models import transformer

    cfg, model = small.config(name)
    params = _init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                cfg["vocab_size"])
    want, _ = transformer.forward(model, params, {"tokens": tokens})
    got = spec.family(cfg["family"]).forward(cfg, params, tokens)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("name", ["gpt_small", "smollm_135m"])
def test_layout_matches_program(name):
    from bench import program

    cfg, model = small.config(name)
    # raises where the layout differs
    program.model_config(cfg, spec.family(cfg["family"]), model)


def test_slim_adam_matches_program():
    """Three steps of the reference SlimAdam against the program's Trainer
    with the jnp backend on the same weights and batches, under the
    harness's warmup schedule, two steps long so that steps 2 and 3 move
    the weights by more than rounding."""
    from repro.train import Trainer, TrainerConfig

    from bench.train import learning_rate

    cfg, model = small.config("gpt_small")
    hp = {"lr": 1e-3, "warmup_steps": 2, "b1": 0.9, "b2": 0.95,
          "eps": 1e-8, "weight_decay": 0.1, "grad_clip": 1.0}
    data = traffic.ZipfBatches(cfg["vocab_size"], 32, 2, 5, alpha=1.2,
                               n_states=16, mix=0.5, continuations=8)
    params = _init(cfg, jax.random.PRNGKey(3))
    tr = Trainer(model, "slim", learning_rate(hp), data,
                 TrainerConfig(total_steps=3, log_every=1, backend="jnp"))
    tr.params = params
    tr.run(3)
    batches = [(data.batch(s)["tokens"], data.batch(s)["labels"]) for s in range(3)]
    losses, _, p3 = adam.train_steps(cfg, spec.family("gpt2").forward, params,
                                     batches, hp, cfg["slim_dims"], rows=1)
    np.testing.assert_allclose(losses, [m["loss"] for m in tr.metrics_log],
                               rtol=1e-5)
    gap = correct.norm_gap(correct.diff_norms(tr.params, params),
                           correct.diff_norms(p3, params))
    assert gap < 1e-4


def test_slim_dims_compress_the_second_moment():
    cfg, _ = small.config("gpt_small")
    params = _init(cfg, jax.random.PRNGKey(0))
    names = common.leaf_names(params)
    assert set(cfg["slim_dims"]) == set(names)
    wv = params["blocks"]["slot_0"]["attn"]["wv"]
    dims = tuple(cfg["slim_dims"]["blocks.slot_0.attn.wv"])
    assert jnp.mean(wv * wv, axis=dims, keepdims=True).shape == wv.shape[:2] + (1, 1)
