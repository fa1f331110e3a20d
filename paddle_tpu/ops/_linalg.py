"""Raw linear-algebra ops.

Reference parity: paddle.linalg surface (python/paddle/tensor/linalg.py →
phi kernels; norm, svd, qr, cholesky, inverse, solve, einsum).  Dense
decompositions route to jax.numpy.linalg (XLA custom calls on TPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def norm(x, p=None, axis=None, keepdim=False):
    if p is None:
        p = "fro" if axis is None or isinstance(axis, (list, tuple)) else 2
    if axis is None and p == "fro":
        return jnp.sqrt(jnp.sum(jnp.square(x)))
    if isinstance(axis, (list, tuple)):
        return jnp.linalg.norm(x, ord=p, axis=tuple(axis), keepdims=keepdim)
    if p == float("inf"):
        return jnp.max(jnp.abs(x), axis=axis, keepdims=keepdim)
    if p == float("-inf"):
        return jnp.min(jnp.abs(x), axis=axis, keepdims=keepdim)
    if p == 0:
        return jnp.sum((x != 0).astype(x.dtype), axis=axis, keepdims=keepdim)
    return jnp.sum(jnp.abs(x) ** p, axis=axis, keepdims=keepdim) ** (1.0 / p)


def vector_norm(x, p=2, axis=None, keepdim=False):
    return norm(x, p=p, axis=axis, keepdim=keepdim)


def matrix_norm(x, p="fro", axis=(-2, -1), keepdim=False):
    return jnp.linalg.norm(x, ord=p, axis=tuple(axis), keepdims=keepdim)


def einsum(equation, *operands):
    return jnp.einsum(equation, *operands)


def transpose_last(x):
    return jnp.swapaxes(x, -1, -2)


def cholesky(x, upper=False):
    L = jnp.linalg.cholesky(x)
    return jnp.swapaxes(L, -1, -2) if upper else L


def inv(x):
    return jnp.linalg.inv(x)


def pinv(x, rcond=1e-15, hermitian=False):
    return jnp.linalg.pinv(x, rtol=rcond, hermitian=hermitian)


def det(x):
    return jnp.linalg.det(x)


def slogdet(x):
    sign, logabs = jnp.linalg.slogdet(x)
    return jnp.stack([sign, logabs])


def qr(x, mode="reduced"):
    return jnp.linalg.qr(x, mode=mode)


def svd(x, full_matrices=False):
    return jnp.linalg.svd(x, full_matrices=full_matrices)


def eigh(x, UPLO="L"):
    return jnp.linalg.eigh(x, UPLO=UPLO)


def eigvalsh(x, UPLO="L"):
    return jnp.linalg.eigvalsh(x, UPLO=UPLO)


def solve(x, y):
    return jnp.linalg.solve(x, y)


def triangular_solve(x, y, upper=True, transpose=False, unitriangular=False):
    return jax.scipy.linalg.solve_triangular(
        x, y, lower=not upper, trans=1 if transpose else 0,
        unit_diagonal=unitriangular)


def cholesky_solve(x, y, upper=False):
    L = y if not upper else jnp.swapaxes(y, -1, -2)
    z = jax.scipy.linalg.solve_triangular(L, x, lower=True)
    return jax.scipy.linalg.solve_triangular(
        jnp.swapaxes(L, -1, -2), z, lower=False)


def lstsq(x, y, rcond=None, driver=None):
    sol, res, rank, sv = jnp.linalg.lstsq(x, y, rcond=rcond)
    return sol, res, rank, sv


def matrix_power(x, n):
    return jnp.linalg.matrix_power(x, n)


def matrix_rank(x, tol=None, hermitian=False):
    return jnp.linalg.matrix_rank(x, rtol=tol)


def cond(x, p=None):
    return jnp.linalg.cond(x, p=p)


def cov(x, rowvar=True, ddof=True, fweights=None, aweights=None):
    return jnp.cov(x, rowvar=rowvar, ddof=1 if ddof else 0,
                   fweights=fweights, aweights=aweights)


def corrcoef(x, rowvar=True):
    return jnp.corrcoef(x, rowvar=rowvar)


def histogramdd(x, bins=10, ranges=None, density=False, weights=None):
    hist, edges = jnp.histogramdd(x, bins=bins, range=ranges,
                                  density=density, weights=weights)
    return hist, list(edges)


def tensordot(x, y, axes=2):
    return jnp.tensordot(x, y, axes=axes)


def lu(x, pivot=True, get_infos=False):
    """paddle.linalg.lu: returns (LU, pivots[, infos]) — LAPACK-style
    packed LU with 1-based pivots (paddle convention)."""
    import jax.scipy.linalg as jsl
    lu_, piv = jsl.lu_factor(x)
    piv = piv.astype(jnp.int32) + 1
    if get_infos:
        infos = jnp.zeros(x.shape[:-2], jnp.int32)
        return lu_, piv, infos
    return lu_, piv


def cdist(x, y, p=2.0, compute_mode="use_mm_for_euclid_dist_if_necessary"):
    """Pairwise p-norm distances [..., M, N] between [..., M, D] and
    [..., N, D] (MXU path for p=2: the |x|^2 - 2xy + |y|^2 expansion)."""
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    if p == 2.0 and str(compute_mode) in (
            "use_mm_for_euclid_dist_if_necessary",
            "use_mm_for_euclid_dist"):
        x2 = jnp.sum(x * x, -1)[..., :, None]
        y2 = jnp.sum(y * y, -1)[..., None, :]
        xy = jnp.matmul(x, jnp.swapaxes(y, -1, -2))
        return jnp.sqrt(jnp.maximum(x2 - 2 * xy + y2, 0.0))
    d = x[..., :, None, :] - y[..., None, :, :]
    if p == float("inf"):
        return jnp.max(jnp.abs(d), -1)
    return jnp.sum(jnp.abs(d) ** p, -1) ** (1.0 / p)


def pdist(x, p=2.0):
    """Condensed pairwise distances of [N, D] (upper triangle, paddle
    pdist contract)."""
    n = x.shape[0]
    full = cdist(x, x, p=p)
    iu, ju = np.triu_indices(n, k=1)
    return full[iu, ju]


def vander(x, n=None, increasing=False):
    return jnp.vander(x, N=n, increasing=increasing)


def matrix_transpose(x):
    return jnp.swapaxes(x, -1, -2)


def matrix_exp(x):
    import jax.scipy.linalg as jsl
    if x.ndim == 2:
        return jsl.expm(x)
    batch = x.shape[:-2]
    flat = x.reshape((-1,) + x.shape[-2:])
    out = jax.vmap(jsl.expm)(flat)
    return out.reshape(batch + x.shape[-2:])


def svdvals(x):
    return jnp.linalg.svd(x, compute_uv=False)


def eig(x):
    """paddle.linalg.eig: general (non-symmetric) eigendecomposition.

    TPU/XLA has no nonsymmetric-eig unit; the reference routes this to
    LAPACK geev on host too, so a host callback loses nothing — the op
    is O(n^3) scalar-sequential and tiny next to any training step.
    """
    cdt = jnp.complex64 if x.dtype in (jnp.float32, jnp.complex64) \
        else jnp.complex128

    def host(a):
        w, v = np.linalg.eig(np.asarray(a))
        return w.astype(cdt), v.astype(cdt)

    if isinstance(x, jax.core.Tracer):
        # under jit: host callback (no TPU generation has a
        # nonsymmetric-eig unit)
        out_shape = (jax.ShapeDtypeStruct(x.shape[:-1], cdt),
                     jax.ShapeDtypeStruct(x.shape, cdt))
        return jax.pure_callback(host, out_shape, x,
                                 vmap_method="sequential")
    w, v = host(jax.device_get(x))
    return jnp.asarray(w), jnp.asarray(v)


def eigvals(x):
    return eig(x)[0]


def householder_product(x, tau):
    """paddle.linalg.householder_product: assemble Q from the reflectors
    LAPACK-packed in ``x`` (below-diagonal) and scales ``tau`` (orgqr).
    The reflector count is static, so the loop unrolls into k rank-1
    updates — each a matmul XLA fuses; no LAPACK needed on device."""
    if x.ndim > 2:
        return jax.vmap(householder_product)(x, tau)
    m, n = x.shape
    k = tau.shape[-1]
    rows = jnp.arange(m)
    q = jnp.eye(m, n, dtype=x.dtype)
    conj = jnp.conj if jnp.iscomplexobj(x) else (lambda a: a)
    for i in reversed(range(k)):
        v = jnp.where(rows == i, 1.0, jnp.where(rows > i, x[:, i], 0.0))
        q = q - tau[i] * jnp.outer(v, conj(v) @ q)
    return q


def ormqr(x, tau, y, left=True, transpose=False):
    """paddle.linalg.ormqr: multiply ``y`` by the Q of (x, tau)."""
    m = x.shape[-2]
    k = tau.shape[-1]
    if x.ndim > 2:
        return jax.vmap(lambda a, t, b: ormqr(a, t, b, left, transpose))(
            x, tau, y)
    # build the FULL m x m Q (householder_product's m x n panel is not
    # enough to multiply arbitrary y): same reflector loop over I_m
    rows = jnp.arange(m)
    qf = jnp.eye(m, dtype=x.dtype)
    conj = jnp.conj if jnp.iscomplexobj(x) else (lambda a: a)
    for i in reversed(range(k)):
        v = jnp.where(rows == i, 1.0, jnp.where(rows > i, x[:, i], 0.0))
        qf = qf - tau[i] * jnp.outer(v, conj(v) @ qf)
    qm = jnp.swapaxes(conj(qf), -1, -2) if transpose else qf
    return qm @ y if left else y @ qm


def lu_unpack(lu_data, lu_pivots, unpack_ludata=True, unpack_pivots=True):
    """paddle.linalg.lu_unpack: (P, L, U) from packed LU + 1-based
    sequential transposition pivots."""
    m, n = lu_data.shape[-2], lu_data.shape[-1]
    if lu_data.ndim > 2:
        return jax.vmap(
            lambda d, p: lu_unpack(d, p, unpack_ludata, unpack_pivots))(
                lu_data, lu_pivots)
    k = min(m, n)
    L = U = P = None
    if unpack_ludata:
        L = jnp.tril(lu_data[:, :k], -1) + jnp.eye(m, k, dtype=lu_data.dtype)
        U = jnp.triu(lu_data[:k, :])
    if unpack_pivots:
        perm = jnp.arange(m)
        for i in range(lu_pivots.shape[-1]):
            j = lu_pivots[i] - 1
            pi, pj = perm[i], perm[j]
            perm = perm.at[i].set(pj).at[j].set(pi)
        # rows of P: P[perm[i], i] = 1 reverses the row swaps
        P = jnp.zeros((m, m), lu_data.dtype).at[perm, jnp.arange(m)].set(1.0)
    return P, L, U


def _lowrank_svd(x, q, niter, M=None):
    """Randomized range-finder SVD (Halko et al.) — q+oversample matmuls
    only, all MXU; deterministic seed (paddle's is seed-dependent too)."""
    a = x - M if M is not None else x
    m, n = a.shape[-2], a.shape[-1]
    p = min(q + 6, n)
    g = jax.random.normal(jax.random.PRNGKey(0), a.shape[:-2] + (n, p),
                          dtype=a.dtype)
    y = a @ g
    for _ in range(niter):
        y = a @ (jnp.swapaxes(a, -1, -2) @ y)
    Q, _ = jnp.linalg.qr(y)
    b = jnp.swapaxes(Q, -1, -2) @ a
    u, s, vh = jnp.linalg.svd(b, full_matrices=False)
    u = Q @ u
    return u[..., :q], s[..., :q], jnp.swapaxes(vh, -1, -2)[..., :q]


def svd_lowrank(x, q=6, niter=2, M=None):
    return _lowrank_svd(x, q, niter, M=M)


def pca_lowrank(x, q=None, center=True, niter=2):
    if q is None:
        q = min(6, x.shape[-2], x.shape[-1])
    M = jnp.mean(x, axis=-2, keepdims=True) if center else None
    return _lowrank_svd(x, q, niter, M=jnp.broadcast_to(M, x.shape)
                        if M is not None else None)
