"""The elastic step weights and sums the micro-items' gradients inside its grad
program (``VirtualCluster._batched_grad_fn``); the host only adds the
partial sums of several micro-batch size buckets.

Each bucket's program, called with a one-hot ``weights``, returns one item's
gradient exactly (the other items add signed zeros), so the per-item
gradients here are the very ones the step sums.  The step's gradient must
equal their host fold in the seed's (micro, rank) order to float32
round-off, and ``grad_weights`` set between two steps (the planted
``half_batch`` fault's path) must take effect without a recompile.
"""
import jax
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.core.cluster import VirtualCluster
from repro.models import registry as R

CFG = R.tiny_config("dense", num_layers=4, dropout_rate=0.1)
EPS = np.finfo(np.float32).eps


def one_bucket():
    """dp=4, one micro-batch: four items of two samples, one bucket."""
    return VirtualCluster(CFG, dp=4, pp=2, global_batch=8, num_micro=1,
                          seq_len=16, seed=0)


def uneven():
    """dp=4 less one rank, two micro-batches of 8: sizes [3,3,2], so two
    buckets (four items of 3, two of 2)."""
    cl = VirtualCluster(CFG, dp=4, pp=2, global_batch=16, num_micro=2,
                        seq_len=16, seed=0)
    cl.recover_fail_stop(1, 1)
    assert cl.per_rank_mbs == [3, 3, 2]
    return cl


CASES = {"one_bucket": (one_bucket, 1), "uneven_3_3_2": (uneven, 2)}


def per_item(cl):
    """``(rank, loss, flat gradient)`` of every item of the next step, in
    the seed's order, read from the step's own programs; each also checked
    against the item's own per-item jit (a different XLA program, so to
    round-off), which a fold that mixed the items fails."""
    grad_one = jax.jit(jax.value_and_grad(cl._loss_fn, argnums=(0, 1, 2)))
    items, calls, _ = cl._grad_calls(cl.step_count)
    out = [None] * len(items)
    for idxs, fn, args in calls:
        stem, layers, head, toks, _, base_key, step, sids, _ = args
        step_key = jax.random.fold_in(base_key, step)
        for j, k in enumerate(idxs):
            onehot = np.zeros(len(idxs), np.float32)
            onehot[j] = 1.0
            losses, g = fn(*args[:-1], onehot)
            g = np.asarray(g)
            _, ref = grad_one(stem, layers, head, toks[j], toks[j], step_key,
                              sids[j])
            ref = np.asarray(ravel_pytree(ref)[0])
            assert np.linalg.norm(g - ref) <= 1e-5 * np.linalg.norm(ref)
            out[k] = (items[k][0], float(losses[j]), g)
    return out


def host_fold(cl, rows):
    """The seed's accumulation: ``g*w`` summed on the host in item order,
    and the bound of its float32 round-off against any other order."""
    acc, mag, loss = None, None, 0.0
    for r, item_loss, g in rows:
        w = cl.grad_weights[r] / cl.num_micro
        gw = g * np.float32(w)
        acc = gw if acc is None else acc + gw
        mag = np.abs(gw) if mag is None else mag + np.abs(gw)
        loss += item_loss * w
    return loss, acc, len(rows) * EPS * mag


@pytest.mark.parametrize("case", sorted(CASES))
def test_step_gradient_is_the_host_fold_of_the_items(case):
    make, n_buckets = CASES[case]
    cl = make()
    _, calls, _ = cl._grad_calls(cl.step_count)
    assert len(calls) == n_buckets
    rows = per_item(cl)
    want_loss, want, bound = host_fold(cl, rows)
    loss, got = cl._micro_grads(cl.step_count)
    assert got.dtype == np.float32 and got.shape == want.shape
    assert np.all(np.abs(got - want) <= bound)
    assert loss == pytest.approx(want_loss, rel=1e-6)
    assert np.abs(want).max() > 0


@pytest.mark.parametrize("case", sorted(CASES))
def test_grad_weights_set_between_steps_take_effect(case):
    """Rank 0 alone weighted (the ``half_batch`` fault): the next step's
    gradient is rank 0's items' alone, from the program already compiled."""
    cl = CASES[case][0]()
    cl.train_step()
    cl.grad_weights = [1.0] + [0.0] * (len(cl.grad_weights) - 1)
    rows = per_item(cl)
    _, calls, _ = cl._grad_calls(cl.step_count)
    compiled = [fn._cache_size() for _, fn, _ in calls]
    _, got = cl._micro_grads(cl.step_count)
    assert [fn._cache_size() for _, fn, _ in calls] == compiled
    # weights 1/num_micro and 0 scale exactly: the sum is rank 0's items'
    # bit for bit
    want = None
    for r, _, g in rows:
        if r == 0:
            gw = g * np.float32(1.0 / cl.num_micro)
            want = gw if want is None else want + gw
    np.testing.assert_array_equal(got, want)
    assert len(rows) > sum(r == 0 for r, _, _ in rows)
