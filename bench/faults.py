"""Faults planted under the timed path, to show that ``correct`` catches
them: each takes the trainer or the engine a run built and breaks it in
place. ``bench/calibrate.py --fault <name>`` reads one on the chip at a
cell's own size; ``bench/tests/test_cells.py`` at a small size."""
from __future__ import annotations


def frozen_state(trainer):
    """The step returns its parameters and optimizer state unchanged."""
    step = trainer._train_step
    trainer._train_step = lambda p, o, b: (p, o, step(p, o, b)[2])


def half_batch(trainer):
    """Half of every batch is left out; the loss is the mean over the rest."""
    data = trainer.data

    class Half:
        def batch(self, step):
            b = data.batch(step)
            return {k: v[: len(v) // 2] for k, v in b.items()}

    trainer.data = Half()


def altered_token(engine):
    """Every sampled token is replaced by its neighbour in the vocabulary."""
    sample = engine._sample_one
    engine._sample_one = lambda st, row: (sample(st, row) + 1) % engine.cfg.vocab_size


def frozen_pool(engine):
    """Decode steps store no K/V: each runs on a copy of the page pools,
    which it consumes, and hands back the pools as they were before it (the
    program donates the pools it is given, so returning those would hand
    back deleted buffers). Prefill still writes the prompt's K/V, so later
    decode steps attend over missing entries and their logits go wrong."""
    import jax
    import jax.numpy as jnp

    decode = engine._decode

    def step(params, state, tokens):
        copy = jax.tree.map(jnp.copy, state.pools)
        logits, ok, _ = decode(params, state._replace(pools=copy), tokens)
        return logits, ok, state

    engine._decode = step


def wrong_slot(engine):
    """Decode steps read and write each row's pages through the next row's
    page table: every live slot attends over another slot's context."""
    import jax.numpy as jnp

    decode = engine._decode

    def step(params, state, tokens):
        return decode(params, state._replace(table=jnp.roll(state.table, 1, 0)),
                      tokens)

    engine._decode = step


TRAIN = {"frozen_state": frozen_state, "half_batch": half_batch}
SERVE = {"altered_token": altered_token, "frozen_pool": frozen_pool,
         "wrong_slot": wrong_slot}
