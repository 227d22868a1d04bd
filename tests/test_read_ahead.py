"""The scanned train loop reads and stacks the next window's batches
while the device runs the current one, inside a call and across calls on
one iterator (base_estimator._run_looped, _read_ahead): same batches in
the same order, same programs, same failures; only the waiting moves.

The estimator is conftest's `slow_step_estimator`: a device step that
takes milliseconds, so that a window is still in flight while the host
reads (the CPU's dispatch is asynchronous too); the source numbers its batches (`numbered_source`)."""

import time

import jax
import numpy as np
import pytest

from euler_tpu import obs

K = 4
SPIN = 150     # ~6 ms a step, ~25 ms a window


def _leaves(est):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(
        (est.state.params, est.state.opt_state))]


def _same(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def _watch(est):
    """Every loss the estimator's two programs return, in step order."""
    losses = []
    step_fn, loop_fn = est._build_train_step(), est._build_train_loop()

    def step(state, batch):
        state, loss, metric = step_fn(state, batch)
        losses.append(loss)
        return state, loss, metric

    def loop(state, batches, static_batch):
        state, ls, ms = loop_fn(state, batches, static_batch)
        losses.append(ls)
        return state, ls, ms

    est._train_step, est._train_loop = step, loop
    return lambda: np.concatenate([np.atleast_1d(np.asarray(x))
                                   for x in losses])


def _serial_loop(est, it, max_steps):
    """The loop as it was before the read-ahead, the plain form: a
    window's batches pulled, stacked, dispatched and waited for, one
    phase after the other; a tail by single steps."""
    step_fn, loop_fn = est._build_train_step(), est._build_train_loop()
    step, losses, buf = 0, [], [next(it)]
    while step < max_steps:
        want = min(K, max_steps - step)
        try:
            while len(buf) < want:
                buf.append(next(it))
        except StopIteration:
            pass
        if not buf:
            break
        if len(buf) == K:
            stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *buf)
            est.state, ls, _ = loop_fn(est.state, stacked, {})
            losses.append(np.asarray(ls))
        else:
            for b in buf:
                est.state, loss, _ = step_fn(est.state, b)
                losses.append(np.asarray(loss)[None])
        step += len(buf)
        buf = []
    return step, np.concatenate(losses)


def _batches(est):
    snap = obs.snapshot()["estimator_window_batches_total"]["values"]
    return {how: snap.get(f"estimator={est._obs_name},how={how}", 0)
            for how in ("ahead", "waited")}


def _mean(lo, hi):
    return sum(range(lo, hi)) / (hi - lo)


@pytest.mark.parametrize("max_steps", [3 * K, 2 * K + 2])
def test_one_call_is_the_serial_loop_bit_for_bit(
        slow_step_estimator, numbered_source, max_steps):
    """(a) Windows read ahead inside one call leave the parameters, the
    optimizer state, every loss and the global step that the serial loop
    leaves, and that single steps leave."""
    plain = slow_step_estimator(SPIN, steps_per_loop=K)
    steps, want_losses = _serial_loop(plain, numbered_source()(), max_steps)
    assert steps == max_steps

    est = slow_step_estimator(SPIN, steps_per_loop=K)
    losses = _watch(est)
    res = est.train(numbered_source(), max_steps=max_steps)
    assert res["global_step"] == max_steps
    assert res["metric"] == pytest.approx(_mean(0, max_steps))
    _same(_leaves(est), _leaves(plain))
    np.testing.assert_array_equal(losses(), want_losses)
    assert res["loss"] == want_losses[-1]

    single = slow_step_estimator(SPIN)
    losses1 = _watch(single)
    res1 = single.train(numbered_source(), max_steps=max_steps)
    assert res1["global_step"] == max_steps
    _same(_leaves(est), _leaves(single))
    np.testing.assert_array_equal(losses1(), want_losses)


def test_calls_on_one_iterator_carry_what_was_read_ahead(
        slow_step_estimator, numbered_source):
    """(b) train(it, max_steps=step + K) again and again on one iterator
    object: step s trains on the s-th batch, nothing is lost between the
    calls, and from the second call on every batch of a window was in
    hand before the window began."""
    src = numbered_source()
    it = src()
    est = slow_step_estimator(SPIN, steps_per_loop=K)
    plain = slow_step_estimator(SPIN, steps_per_loop=K)
    _serial_loop(plain, numbered_source()(), 5 * K)
    for call in range(5):
        before = _batches(est)
        obs.clear_trace()
        res = est.train(it, max_steps=(call + 1) * K)
        assert res["global_step"] == (call + 1) * K
        # the batch's number is the step's metric
        assert res["metric"] == pytest.approx(_mean(call * K,
                                                    (call + 1) * K))
        after = _batches(est)
        got = {how: after[how] - before[how] for how in after}
        assert got == ({"ahead": 0, "waited": K} if call == 0
                       else {"ahead": K, "waited": 0}), (call, got)
        # the whole next window was read, and stacked, under this one
        assert src.pulled == (call + 2) * K
        kept_it, raw, stacked, ended = est._ahead
        assert kept_it is it and len(raw) == K and not ended
        assert [int(b["n"][0]) for b in raw] == list(
            range(src.pulled - K, src.pulled))
        assert stacked["n"].shape == (K, 1)
        spans = obs.default_tracer().spans()
        (dispatch,) = [s for s in spans if s.name == "train_dispatch"]
        before_it = [s.name for s in spans
                     if s.name in ("input_wait", "stack")
                     and s.parent_id in (dispatch.span_id,
                                         dispatch.parent_id)]
        # a carried window is neither waited for nor stacked again
        assert before_it == ([] if call else
                             ["input_wait", "input_wait", "stack"])
        assert sum(s.name == "read_ahead" for s in spans) == 1
    _same(_leaves(est), _leaves(plain))


def test_a_carried_window_serves_calls_that_want_fewer(
        slow_step_estimator, numbered_source):
    """Rule 6: after a scanned window read K ahead and stacked them, calls
    of one step each (and then a window) take them in order, raw."""
    src = numbered_source()
    it = src()
    est = slow_step_estimator(SPIN, steps_per_loop=K)
    est.train(it, max_steps=K)
    assert src.pulled == 2 * K and est._ahead[2] is not None
    for step in (K + 1, K + 2):
        res = est.train(it, max_steps=step)
        assert res["global_step"] == step
        assert res["metric"] == step - 1
    assert src.pulled == 2 * K          # served from what was in hand
    res = est.train(it, max_steps=2 * K + 2)
    assert res["global_step"] == 2 * K + 2
    assert res["metric"] == pytest.approx(_mean(K + 2, 2 * K + 2))
    plain = slow_step_estimator(SPIN, steps_per_loop=1)
    plain.train(numbered_source(), max_steps=2 * K + 2)
    np.testing.assert_allclose(_leaves(est)[0], _leaves(plain)[0],
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("max_steps", [3 * K, 2 * K + 2, K, 1])
def test_a_callable_gives_exactly_the_batches_trained_on(
        slow_step_estimator, numbered_source, max_steps):
    """(c) An iterator made from a callable dies with the call: nothing
    is read from it beyond max_steps, so the call's last window reads
    nothing ahead, and nothing is kept."""
    src = numbered_source()
    est = slow_step_estimator(SPIN, steps_per_loop=K)
    obs.clear_trace()
    res = est.train(src, max_steps=max_steps)
    assert res["global_step"] == max_steps
    assert src.pulled == max_steps
    assert est._ahead is None
    ahead = [s for s in obs.default_tracer().spans()
             if s.name == "read_ahead"]
    full = max_steps // K
    last = full if max_steps % K else full - 1
    assert [s.attrs["step"] for s in ahead] == [
        K * w for w in range(1, last + 1)]
    assert [s.attrs["got"] for s in ahead] == [
        min(K, max_steps - s.attrs["step"]) for s in ahead]


def test_another_iterator_drops_the_carry(slow_step_estimator,
                                          numbered_source):
    """(d) What was read ahead belongs to the iterator it came from."""
    src1, src2 = numbered_source(), numbered_source()
    it1, it2 = src1(), src2()
    est = slow_step_estimator(SPIN, steps_per_loop=K)
    est.train(it1, max_steps=K)
    assert est._ahead[0] is it1 and src1.pulled == 2 * K
    res = est.train(it2, max_steps=2 * K)
    assert res["global_step"] == 2 * K
    assert res["metric"] == pytest.approx(_mean(0, K))   # it2's first K
    assert est._ahead[0] is it2
    assert src1.pulled == 2 * K and src2.pulled == 2 * K


@pytest.mark.parametrize("across_calls", [True, False])
def test_a_stream_that_ends_while_read_ahead(slow_step_estimator,
                                             numbered_source, across_calls):
    """(e) StopIteration while reading ahead ends the stream for the next
    window, never the one in flight: its steps are counted, the tail is
    trained by single steps, then training stops."""
    src = numbered_source(stop_at=K + 2)
    it = src()
    est = slow_step_estimator(SPIN, steps_per_loop=K)
    if across_calls:
        res = est.train(it, max_steps=K)
        assert res["global_step"] == K
        _, raw, stacked, ended = est._ahead
        assert len(raw) == 2 and stacked is None and ended
    res = est.train(it, max_steps=3 * K)
    assert res["global_step"] == K + 2
    assert src.pulled == K + 2
    with pytest.raises(StopIteration):   # as a drained iterator always did
        est.train(it, max_steps=3 * K)
    plain = slow_step_estimator(SPIN, steps_per_loop=K)
    steps, _ = _serial_loop(plain, numbered_source(stop_at=K + 2)(), 3 * K)
    assert steps == K + 2
    _same(_leaves(est), _leaves(plain))


def test_a_retryable_failure_while_read_ahead_is_retried(
        slow_step_estimator, numbered_source):
    """(f) _next_input's retry runs under the read-ahead as under a
    window's own wait: one failure, one retry, no batch skipped, and the
    steps go on in order."""
    src = numbered_source(fail_at=K + 1)
    est = slow_step_estimator(SPIN, steps_per_loop=K)
    obs.clear_trace()
    res = est.train(src, max_steps=3 * K)
    assert res["global_step"] == 3 * K and src.pulled == 3 * K
    assert res["metric"] == pytest.approx(_mean(0, 3 * K))
    health = est.input_health
    assert (health["input_failures"], health["input_retries"],
            health["skipped_batches"]) == (1, 1, 0)
    spans = {s.span_id: s for s in obs.default_tracer().spans()}
    (backoff,) = [s for s in spans.values()
                  if s.name == "input_retry_backoff"]
    wait = spans[backoff.parent_id]
    assert wait.name == "input_wait"
    assert spans[wait.parent_id].name == "read_ahead"


def test_an_unrecoverable_failure_while_read_ahead_keeps_the_window(
        slow_step_estimator, numbered_source, tmp_path):
    """Rule 5: a caller's iterator cannot be recreated, so its failure is
    raised; the emergency checkpoint holds the steps of the window that
    was in flight."""
    src = numbered_source(fail_at=K + 1)
    est = slow_step_estimator(SPIN, steps_per_loop=K)
    est.model_dir = str(tmp_path)
    with pytest.raises(OSError, match="planted"):
        est.train(src(), max_steps=3 * K)
    assert est.input_health["emergency_checkpoint_step"] == K
    assert int(est.state.step) == K


def test_a_slow_source_is_waited_for_no_longer_than_before(
        slow_step_estimator, numbered_source):
    """(g) With a source slower than the step the loop is as input-bound
    as it was: reading ahead stops once the window in flight is done
    (is_ready), so a call returns no later than the serial loop plus one
    batch's production, and reads at most that one batch more."""
    delay = 0.004
    plain = slow_step_estimator(0, steps_per_loop=K)
    warm = slow_step_estimator(0, steps_per_loop=K)
    est = slow_step_estimator(0, steps_per_loop=K)
    _serial_loop(warm, numbered_source()(), K)       # compiles
    t0 = time.perf_counter()
    _serial_loop(plain, numbered_source(delay_s=delay)(), 3 * K)
    serial_s = time.perf_counter() - t0
    src = numbered_source(delay_s=delay)
    est.train(numbered_source()(), max_steps=0)
    t0 = time.perf_counter()
    res = est.train(src(), max_steps=3 * K)
    ahead_s = time.perf_counter() - t0
    assert res["global_step"] == 3 * K
    assert 3 * K <= src.pulled <= 3 * K + K
    assert ahead_s <= serial_s + delay + 0.05, (ahead_s, serial_s)
    _same(_leaves(est)[:1], _leaves(plain)[:1])
