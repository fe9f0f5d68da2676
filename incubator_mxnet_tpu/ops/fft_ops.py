"""FFT op family + the remaining np.linalg surface.

Reference: MXNet ships ``mx.contrib.ndarray.fft/ifft`` (GPU cuFFT contrib
ops) and the 2.x ``mx.np.linalg`` namespace (``python/mxnet/numpy/
linalg.py``). Here both families are XLA-lowered (TPU FFT is native) and
registered like every other op. Complex results are returned as jax
complex64 arrays wrapped in NDArray — numpy semantics, matching mx.np.
"""

from __future__ import annotations

import jax.numpy as jnp

from .registry import register


# --- fft ---------------------------------------------------------------------

@register("fft")
def fft(x, n=None, axis=-1, norm=None):
    return jnp.fft.fft(x, n=n, axis=axis, norm=norm)


@register("ifft")
def ifft(x, n=None, axis=-1, norm=None):
    return jnp.fft.ifft(x, n=n, axis=axis, norm=norm)


@register("rfft")
def rfft(x, n=None, axis=-1, norm=None):
    return jnp.fft.rfft(x, n=n, axis=axis, norm=norm)


@register("irfft")
def irfft(x, n=None, axis=-1, norm=None):
    return jnp.fft.irfft(x, n=n, axis=axis, norm=norm)


@register("fft2")
def fft2(x, s=None, axes=(-2, -1), norm=None):
    return jnp.fft.fft2(x, s=s, axes=tuple(axes), norm=norm)


@register("ifft2")
def ifft2(x, s=None, axes=(-2, -1), norm=None):
    return jnp.fft.ifft2(x, s=s, axes=tuple(axes), norm=norm)


@register("fftn")
def fftn(x, s=None, axes=None, norm=None):
    return jnp.fft.fftn(x, s=s, axes=axes, norm=norm)


@register("ifftn")
def ifftn(x, s=None, axes=None, norm=None):
    return jnp.fft.ifftn(x, s=s, axes=axes, norm=norm)


@register("fftshift")
def fftshift(x, axes=None):
    return jnp.fft.fftshift(x, axes=axes)


@register("ifftshift")
def ifftshift(x, axes=None):
    return jnp.fft.ifftshift(x, axes=axes)


@register("real")
def real(x):
    return jnp.real(x)


@register("imag")
def imag(x):
    return jnp.imag(x)


@register("conj")
def conj(x):
    return jnp.conj(x)


@register("angle")
def angle(x):
    return jnp.angle(x)


@register("absolute_complex", aliases=("complex_abs",))
def absolute_complex(x):
    return jnp.abs(x)


# --- np.linalg completions ---------------------------------------------------

@register("linalg_norm")
def linalg_norm(x, ord=None, axis=None, keepdims=False):
    return jnp.linalg.norm(x, ord=ord, axis=axis, keepdims=keepdims)


@register("linalg_solve")
def linalg_solve(a, b):
    return jnp.linalg.solve(a, b)


@register("linalg_lstsq", differentiable=False)
def linalg_lstsq(a, b, rcond=None):
    return tuple(jnp.linalg.lstsq(a, b, rcond=rcond))


@register("linalg_qr")
def linalg_qr(a, mode="reduced"):
    # mode='r' returns a single array; 'reduced'/'complete' return (q, r).
    # jnp returns a QRResult NamedTuple — convert to a plain tuple so the
    # tape's vjp cotangent structure matches (invoke reconstructs plain
    # tuples on backward).
    out = jnp.linalg.qr(a, mode=mode)
    return tuple(out) if isinstance(out, tuple) else out


@register("linalg_svd")
def linalg_svd(a, full_matrices=True, compute_uv=True):
    out = jnp.linalg.svd(a, full_matrices=full_matrices,
                         compute_uv=compute_uv)
    return tuple(out) if isinstance(out, tuple) else out


@register("linalg_eigh")
def linalg_eigh(a, UPLO="L"):
    return tuple(jnp.linalg.eigh(a, UPLO=UPLO))


@register("linalg_eigvalsh")
def linalg_eigvalsh(a, UPLO="L"):
    return jnp.linalg.eigvalsh(a, UPLO=UPLO)


@register("linalg_cholesky")
def linalg_cholesky(a):
    return jnp.linalg.cholesky(a)


@register("linalg_pinv")
def linalg_pinv(a, rcond=None):
    return jnp.linalg.pinv(a, rcond=rcond)


@register("linalg_matrix_rank", differentiable=False)
def linalg_matrix_rank(a, tol=None):
    return jnp.linalg.matrix_rank(a, tol=tol)


@register("linalg_matrix_power")
def linalg_matrix_power(a, n=1):
    return jnp.linalg.matrix_power(a, n)


@register("linalg_multi_dot")
def linalg_multi_dot(*arrays):
    return jnp.linalg.multi_dot(arrays)


@register("linalg_cond", differentiable=False)
def linalg_cond(a, p=None):
    return jnp.linalg.cond(a, p=p)


@register("linalg_tensorsolve")
def linalg_tensorsolve(a, b):
    return jnp.linalg.tensorsolve(a, b)


@register("linalg_tensorinv")
def linalg_tensorinv(a, ind=2):
    return jnp.linalg.tensorinv(a, ind=ind)
