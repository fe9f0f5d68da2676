"""``ops/state_space.py``: the causal depthwise convolution and the
selective scan, whole-sequence and one-token forms, against the
recurrence written out one position at a time. Small, float32, the CPU."""

import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu.ops import state_space

B, E, N, K = 2, 24, 5, 4
BUCKET = 16


def _inputs(t, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((B, t, E)).astype(dtype)
    dt = np.log1p(np.exp(rng.standard_normal((B, t, E)) - 2)).astype(
        np.float32)
    a = -np.exp(rng.standard_normal((N, E)) * 0.3
                + np.log(np.arange(1, N + 1))[:, None]).astype(np.float32)
    b = rng.standard_normal((B, t, N)).astype(dtype)
    c = rng.standard_normal((B, t, N)).astype(dtype)
    d = (1 + 0.1 * rng.standard_normal(E)).astype(np.float32)
    return x, dt, a, b, c, d


def _by_hand(x, dt, a, b, c, d, h=None):
    """``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t``, ``y_t = C_t h_t + D
    x_t``, in float64 numpy, one position at a time."""
    x, dt, a, b, c, d = (np.asarray(v, np.float64)
                         for v in (x, dt, a, b, c, d))
    h = np.zeros((x.shape[0], N, E)) if h is None else np.asarray(
        h, np.float64)
    ys = []
    for t in range(x.shape[1]):
        h = np.exp(dt[:, t, None, :] * a) * h \
            + (dt[:, t] * x[:, t])[:, None, :] * b[:, t, :, None]
        ys.append(np.einsum("bne,bn->be", h, c[:, t]) + d * x[:, t])
    return np.stack(ys, 1), h


def _close(got, want, tol=1e-5):
    assert np.abs(np.asarray(got) - want).max() < tol * (
        1 + np.abs(want).max())


@pytest.mark.parametrize("t,chunk", [(16, 4), (16, 16), (13, 4), (5, 8),
                                     (1, 4), (64, 64)])
def test_chunked_scan_is_the_one_position_recurrence(t, chunk):
    """``T`` that is and is not a multiple of the chunk, a chunk longer
    than the sequence, one position."""
    args = _inputs(t, seed=t)
    y, h = state_space.scan_sequence(*map(jnp.asarray, args), chunk=chunk)
    want_y, want_h = _by_hand(*args)
    assert y.shape == (B, t, E) and h.shape == (B, N, E)
    assert h.dtype == jnp.float32
    _close(y, want_y)
    _close(h, want_h)


@pytest.mark.parametrize("t,k", [(7, 3), (16, 1), (12, 9)])
def test_sequence_then_steps_is_the_longer_sequence(t, k):
    """The sequence form over ``T`` positions, then ``k`` one-token steps
    from the state it left = the sequence form over ``T + k``; the
    convolution likewise, from the taps it left."""
    x, dt, a, b, c, d = map(jnp.asarray, _inputs(t + k, seed=3))
    y_all, h_all = state_space.scan_sequence(x, dt, a, b, c, d, chunk=4)
    y, h = state_space.scan_sequence(x[:, :t], dt[:, :t], a, b[:, :t],
                                     c[:, :t], d, chunk=4)
    _close(y, np.asarray(y_all[:, :t]))
    for j in range(t, t + k):
        yj, h = state_space.scan_step(h, x[:, j], dt[:, j], a, b[:, j],
                                      c[:, j], d)
        _close(yj, np.asarray(y_all[:, j]))
    _close(h, np.asarray(h_all))
    # the same stretch from a carried state, as a sequence
    _, h0 = state_space.scan_sequence(x[:, :t], dt[:, :t], a, b[:, :t],
                                      c[:, :t], d, chunk=4)
    y_rest, h_rest = state_space.scan_sequence(
        x[:, t:], dt[:, t:], a, b[:, t:], c[:, t:], d, h0=h0, chunk=4)
    _close(y_rest, np.asarray(y_all[:, t:]))
    _close(h_rest, np.asarray(h_all))

    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.standard_normal((K, E)).astype(np.float32))
    bias = jnp.asarray(rng.standard_normal(E).astype(np.float32))
    c_all, taps_all = state_space.conv_sequence(x, w, bias)
    c_head, taps = state_space.conv_sequence(x[:, :t], w, bias)
    _close(c_head, np.asarray(c_all[:, :t]))
    for j in range(t, t + k):
        cj, taps = state_space.conv_step(x[:, j], taps, w, bias)
        _close(cj, np.asarray(c_all[:, j]))
    _close(taps, np.asarray(taps_all))


def test_convolution_is_the_written_out_sum():
    x = _inputs(9, seed=1)[0]
    rng = np.random.default_rng(2)
    w = rng.standard_normal((K, E)).astype(np.float32)
    bias = rng.standard_normal(E).astype(np.float32)
    got, taps = state_space.conv_sequence(jnp.asarray(x), jnp.asarray(w),
                                          jnp.asarray(bias))
    want = np.zeros_like(x, np.float64)
    for t in range(9):
        acc = bias.astype(np.float64)
        for k in range(K):
            if t - K + 1 + k >= 0:
                acc = acc + w[k] * x[:, t - K + 1 + k]
        want[:, t] = acc / (1 + np.exp(-acc))       # silu
    _close(got, want)
    _close(taps, x[:, -(K - 1):])


@pytest.mark.parametrize("n", [1, 2, 3, 5, BUCKET])
def test_state_and_taps_at_the_true_length_of_a_padded_sequence(n):
    """A prompt of ``n`` padded to a bucket with garbage: the state and
    the taps handed to the join are those of the unpadded prompt (zeros
    before position 0 where ``n`` is under ``K - 1``), and so are the
    outputs at the true positions."""
    x, dt, a, b, c, d = map(jnp.asarray, _inputs(BUCKET, seed=n))
    rng = np.random.default_rng(7)
    w = jnp.asarray(rng.standard_normal((K, E)).astype(np.float32))
    bias = jnp.asarray(rng.standard_normal(E).astype(np.float32))
    true = jnp.int32(n)
    y, h = state_space.scan_sequence(x, dt, a, b, c, d, n=true, chunk=4)
    want_y, want_h = state_space.scan_sequence(
        x[:, :n], dt[:, :n], a, b[:, :n], c[:, :n], d, chunk=4)
    _close(h, np.asarray(want_h))
    _close(y[:, :n], np.asarray(want_y))
    out, taps = state_space.conv_sequence(x, w, bias, n=true)
    want_out, want_taps = state_space.conv_sequence(x[:, :n], w, bias)
    assert taps.shape == (B, K - 1, E)
    _close(taps, np.asarray(want_taps))
    _close(out[:, :n], np.asarray(want_out))
    if n < K - 1:
        assert not np.asarray(taps[:, :K - 1 - n]).any()


def test_the_state_stays_float32_under_bfloat16_activations():
    """``x``, ``B``, ``C`` in bfloat16: ``y`` comes back in bfloat16, the
    state float32, and both forms agree with the float64 recurrence over
    the rounded inputs to bfloat16's last place."""
    x, dt, a, b, c, d = _inputs(12, seed=4)
    lo = lambda v: jnp.asarray(v).astype(jnp.bfloat16)
    y, h = state_space.scan_sequence(lo(x), jnp.asarray(dt), jnp.asarray(a),
                                     lo(b), lo(c), jnp.asarray(d), chunk=4)
    assert y.dtype == jnp.bfloat16 and h.dtype == jnp.float32
    f32 = lambda v: np.asarray(lo(v).astype(jnp.float32))
    want_y, want_h = _by_hand(f32(x), dt, a, f32(b), f32(c), d)
    _close(h, want_h, 1e-5)
    _close(y.astype(jnp.float32), want_y, 1e-2)
