"""Manifold-constrained hyper-connections (arXiv:2512.24880, over
arXiv:2409.19606): the residual stream as ``n`` streams, and per token
and sub-layer three sets of coefficients: how the sub-layer's input is
read from the streams (``H_pre``, ``n``), how its output is written back
(``H_post``, ``n``), and a doubly stochastic ``n x n`` mix of the streams
themselves (``H_res``, by Sinkhorn's rounds), in the place of
``x + f(x)``:

    u = H_pre X        y = f(u)        X <- H_res X + H_post^T y

The streams lie ``X`` (n, ..., C): a stream is a contiguous plane, so the
read and the write are ``n`` and ``n * n`` scaled planes added up, and the
coefficients lie (n, ...) and (n, n, ...) with the tokens minor. Every
coefficient is float32; the streams keep their type.

``activate`` turns the ``2n + n * n`` pre-activations of a token into the
coefficients. Its plain statement is ``_activate_plain``; lowered for the
TPU it is one small Pallas kernel (``_activate_kernel``), because XLA
makes of Sinkhorn's 20 rounds some 80 fusions a sub-layer (a sum and a
division a half-round, each a launch for 16 numbers a token), or, written
over 16 separate vectors, a program that takes 2.5 s a sub-layer to
compile (PERF.md PR 32): in the kernel the rounds are a loop over
registers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import pallas_attention


def sinkhorn(m, iters, eps):
    """``m`` (n, n, ...) positive -> doubly stochastic: ``iters`` times the
    columns (sums over axis 0) then the rows (sums over axis 1) divided by
    their sums, ``eps`` added to every sum."""
    n = m.shape[0]
    for _ in range(iters):
        m = m / (sum(m[i] for i in range(n)) + eps)[None]
        m = m / (sum(m[:, j] for j in range(n)) + eps)[:, None]
    return m


def _activate_plain(z, n, iters, eps, clamp):
    pre = jax.nn.sigmoid(z[:n])
    post = 2.0 * jax.nn.sigmoid(z[n:2 * n])
    res = jnp.exp(jnp.clip(z[2 * n:], *clamp)).reshape((n, n) + z.shape[1:])
    return jnp.concatenate([
        pre, post, sinkhorn(res, iters, eps).reshape(z[2 * n:].shape)])


def _activate_kernel(z_ref, o_ref, *, n, iters, eps, clamp):
    sig = lambda a: 1.0 / (1.0 + jnp.exp(-a))
    o_ref[:n] = sig(z_ref[:n])
    o_ref[n:2 * n] = 2.0 * sig(z_ref[n:2 * n])
    # the n x n entries as n * n vectors over the tokens: a round is
    # elementwise over registers
    at = lambda i, j: 2 * n + i * n + j
    m = tuple(jnp.exp(jnp.clip(z_ref[at(i, j):at(i, j) + 1], *clamp))
              for i in range(n) for j in range(n))

    def one_round(_, m):
        cols = [sum(m[i * n + j] for i in range(n)) + eps for j in range(n)]
        m = [m[i * n + j] / cols[j] for i in range(n) for j in range(n)]
        rows = [sum(m[i * n + j] for j in range(n)) + eps for i in range(n)]
        return tuple(m[i * n + j] / rows[i]
                     for i in range(n) for j in range(n))

    m = jax.lax.fori_loop(0, iters, one_round, m)
    for i in range(n):
        for j in range(n):
            o_ref[at(i, j):at(i, j) + 1] = m[i * n + j]


def activate_kernel(z, n, iters, eps, clamp, interpret=None):
    """``activate`` as the kernel, over ``z`` (2n + n*n, N)."""
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = not pallas_attention.pallas_available()
    return pl.pallas_call(
        functools.partial(_activate_kernel, n=n, iters=iters, eps=eps,
                          clamp=clamp),
        out_shape=jax.ShapeDtypeStruct(z.shape, z.dtype),
        interpret=interpret, name="hyper_connection_coefficients")(z)


def activate(z, n, iters, eps, clamp):
    """Pre-activations ``z`` (2n + n*n, ...) float32 -> the coefficients in
    the same rows: ``sigmoid`` of the first ``n`` (``H_pre``), twice the
    ``sigmoid`` of the next ``n`` (``H_post``), and of the last ``n * n``
    ``exp`` of the clipped values made doubly stochastic by ``iters``
    rounds of Sinkhorn (``H_res``, row-major)."""
    plain = functools.partial(_activate_plain, n=n, iters=iters, eps=eps,
                              clamp=clamp)

    def kernel(z):
        flat = z.reshape(z.shape[0], -1)
        return activate_kernel(flat, n, iters, eps, clamp,
                               interpret=False).reshape(z.shape)

    return jax.lax.platform_dependent(z, tpu=kernel, default=plain)


def coefficients(x, w, scale, bias, iters, eps, clamp):
    """``x`` (n, ..., C) streams -> float32 ``H_pre`` (n, ...), ``H_post``
    (n, ...), ``H_res`` (n, n, ...). ``w`` (2n + n*n, n*C) projects the
    streams of a token, RMS-normed over all ``n * C`` values without a
    gain (the norm is one number a token and is applied to the product);
    ``scale`` (3,) and ``bias`` (2n + n*n,) are the three sets' ``a`` and
    ``b``: ``H_pre = sigmoid``, ``H_post = 2 sigmoid``, ``H_res =
    sinkhorn(exp(clip))``."""
    n, c = x.shape[0], x.shape[-1]
    xf = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=(0, -1)) + eps)
    w = w.reshape(-1, n, c)
    raw = sum(jnp.einsum("oc,...c->o...", w[:, i], x[i],
                         preferred_element_type=jnp.float32)
              for i in range(n)) * r
    column = lambda v: v.astype(jnp.float32).reshape(
        (-1,) + (1,) * (raw.ndim - 1))
    z = raw * column(jnp.repeat(scale, np.array([n, n, n * n]))) \
        + column(bias)
    h = activate(z, n, iters, eps, clamp)
    return h[:n], h[n:2 * n], h[2 * n:].reshape((n, n) + raw.shape[1:])


def read(x, pre):
    """The sub-layer's input ``H_pre X`` (..., C), in the streams' type."""
    return sum(pre[i][..., None] * x[i].astype(jnp.float32)
               for i in range(x.shape[0])).astype(x.dtype)


def write(x, res, post, y):
    """``H_res X + H_post^T y``: the streams (n, ..., C) after a sub-layer
    whose output is ``y`` (..., C)."""
    n = x.shape[0]
    xf, yf = x.astype(jnp.float32), y.astype(jnp.float32)
    return jnp.stack([
        sum(res[i, j][..., None] * xf[j] for j in range(n))
        + post[i][..., None] * yf for i in range(n)]).astype(x.dtype)
