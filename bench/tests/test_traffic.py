"""Load generation: same work for every seed, accounting of every token."""
import numpy as np
import pytest

from bench import serve, traffic
from bench.tests import small


def _window(seed, order):
    cell, _ = small.cell("smollm_135m.serve_chat")
    t = dict(cell.traffic, order=order)
    sched = traffic.open_schedule(t, 2.0, 5.0, 30.0, 95.0, 211, seed)
    return [x for x in sched if 5.0 <= x[0] < 35.0]


@pytest.mark.parametrize("order", ["seeded", "fixed"])
def test_every_seed_gets_the_same_window_work(order):
    a, b = _window(1, order), _window(2**40 + 3, order)
    assert len(a) == len(b) == 60
    for k in (lambda x: len(x[1]), lambda x: x[2]):
        assert sorted(map(k, a)) == sorted(map(k, b))
    gaps = [np.diff([x[0] for x in w] + [35.0]) for w in (a, b)]
    np.testing.assert_allclose(np.sort(gaps[0]), np.sort(gaps[1]), rtol=1e-9)
    def shape(w):
        return [(x[0], len(x[1]), x[2]) for x in w]

    assert (shape(a) == shape(b)) == (order == "fixed")   # another order, or fixed
    assert not np.array_equal(a[0][1], b[0][1])  # the token ids: the seed's


def test_zipf_batches_are_a_function_of_seed_and_step():
    z = traffic.ZipfBatches(211, 16, 2, 7, alpha=1.2, n_states=16, mix=0.5,
                            continuations=8)
    b0, b1 = z.batch(0), z.batch(1)
    np.testing.assert_array_equal(b0["tokens"], z.batch(0)["tokens"])
    np.testing.assert_array_equal(b0["tokens"][:, 1:], b0["labels"][:, :-1])
    assert not np.array_equal(b0["tokens"], b1["tokens"])


def test_window_work_counts_every_token():
    cell, model = small.cell("smollm_135m.serve_offline")
    d = serve.EngineSession(cell, 5, model)
    reqs = traffic.Requests(cell.traffic, cell.config["vocab_size"], 5, 4)
    for _ in range(6):
        d.submit(*reqs.next(), due=0.0)
    d.in_window = True
    out0 = d.eng.tokens_out
    for _ in range(12):
        d.step()
    calls, contexts = d.window_work()
    admitted = [r for r in d.reqs.values() if r.admit_step is not None]
    decoded = sum(q == 1 for q, _ in calls)
    assert decoded == d.eng.tokens_out - out0 - len(admitted)
    prompt = sum(len(r.prompt) for r in admitted)
    assert len(contexts) == prompt + decoded
