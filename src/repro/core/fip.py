"""FIP / FFIP inner-product algebra (Pogue & Nicolici, IEEE TC 2023).

Implements, in pure JAX:

  * Eq. (1)  baseline inner product          -> :func:`baseline_matmul`
  * Eq. (2)  Fast Inner Product (FIP)        -> :func:`fip_matmul`
  * Eqs. (3)/(4)  alpha / beta correction terms
  * Eqs. (7)-(9)  Free-pipeline FIP (FFIP)   -> :func:`ffip_matmul`
  * Eq. (9)  y-delta weight encoding         -> :func:`make_y` / :func:`y_to_b`
  * Eqs. (15)-(16)  beta folding into bias   -> :func:`fold_beta_into_bias`,
    :func:`fip_matmul_beta_folded`

All functions are shape-polymorphic over leading batch dims of ``a`` and are
exact (same algebra, reordered) — for integer dtypes the results are
bit-exact against the baseline; for floats they agree to rounding error.

Conventions: the paper uses 1-based indices; ``a_{i,2k-1}`` (odd positions)
maps to ``a[..., 0::2]`` and ``a_{i,2k}`` (even positions) to ``a[..., 1::2]``.
K must be even (callers pad via :mod:`repro.kernels.ops` otherwise).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array


def _check_even_k(k: int) -> None:
    if k % 2 != 0:
        raise ValueError(
            f"FIP/FFIP require an even contraction dim K, got K={k}. "
            "Pad with zeros (repro.kernels.ops handles this) first."
        )


def _acc_dtype(dtype: jnp.dtype) -> jnp.dtype:
    """Accumulation dtype: int32 for sub-32-bit ints, f32 for sub-32-bit floats."""
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.int32 if jnp.dtype(dtype).itemsize < 8 else dtype
    if dtype in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    return dtype


# ---------------------------------------------------------------------------
# Eq. (1): baseline
# ---------------------------------------------------------------------------

def baseline_matmul(a: Array, b: Array, *, precision=jax.lax.Precision.HIGHEST) -> Array:
    """Traditional inner product, Eq. (1). a: (..., M, K), b: (K, N)."""
    acc = _acc_dtype(jnp.result_type(a.dtype, b.dtype))
    return jnp.matmul(a.astype(acc), b.astype(acc), precision=precision)


def _pairs(x: Array, axis: int):
    """(odd, even) pair halves of ``x`` along ``axis`` (0-based positions 0,
    2, 4, ... and 1, 3, 5, ...) via a reshape to a pair axis of size 2: the
    TPU compiler turns stride-2 slices into gathers, this into plain
    slices."""
    axis %= x.ndim
    shp = x.shape
    x = x.reshape(*shp[:axis], shp[axis] // 2, 2, *shp[axis + 1:])
    return (jax.lax.index_in_dim(x, 0, axis + 1, keepdims=False),
            jax.lax.index_in_dim(x, 1, axis + 1, keepdims=False))


# ---------------------------------------------------------------------------
# Eqs. (3) / (4): correction terms
# ---------------------------------------------------------------------------

def fip_alpha(a: Array) -> Array:
    """Eq. (3): alpha_i = sum_j a_{i,2j-1} * a_{i,2j}.  a: (..., M, K) -> (..., M)."""
    _check_even_k(a.shape[-1])
    acc = _acc_dtype(a.dtype)
    a_odd, a_evn = _pairs(a.astype(acc), -1)
    return jnp.sum(a_odd * a_evn, axis=-1)


def fip_beta(b: Array) -> Array:
    """Eq. (4): beta_j = sum_i b_{2i-1,j} * b_{2i,j}.  b: (K, N) -> (N,)."""
    _check_even_k(b.shape[0])
    acc = _acc_dtype(b.dtype)
    b_odd, b_evn = _pairs(b.astype(acc), 0)
    return jnp.sum(b_odd * b_evn, axis=0)


# ---------------------------------------------------------------------------
# Eq. (2): FIP
# ---------------------------------------------------------------------------

def fip_cross_term(a: Array, b: Array, *, k_chunk: int = 0) -> Array:
    """The summation term of Eq. (2) (without the -alpha -beta corrections).

    cross_ij = sum_{k=1..K/2} (a_{i,2k-1} + b_{2k,j}) * (a_{i,2k} + b_{2k-1,j})

    The (.., M, K/2, N) intermediate is materialized; ``k_chunk`` > 0 chunks
    the K/2 axis with a scan to bound memory (used by larger refs/tests).
    """
    _check_even_k(a.shape[-1])
    acc = _acc_dtype(jnp.result_type(a.dtype, b.dtype))
    a = a.astype(acc)
    b = b.astype(acc)
    a_odd, a_evn = _pairs(a, -1)                       # a_{i,2k-1}, a_{i,2k}
    b_odd, b_evn = _pairs(b, 0)                        # b_{2k-1,j}, b_{2k,j}

    def chunk_sum(ao, ae, bo, be):
        t1 = ao[..., :, :, None] + be[None, :, :]      # a_{i,2k-1} + b_{2k,j}
        t2 = ae[..., :, :, None] + bo[None, :, :]      # a_{i,2k}   + b_{2k-1,j}
        return jnp.sum(t1 * t2, axis=-2)

    kh = a_odd.shape[-1]
    if not k_chunk or k_chunk >= kh:
        return chunk_sum(a_odd, a_evn, b_odd, b_evn)

    if kh % k_chunk != 0:
        raise ValueError(f"k_chunk={k_chunk} must divide K/2={kh}")
    n_chunks = kh // k_chunk

    def body(carry, idx):
        sl = lambda x, ax: jax.lax.dynamic_slice_in_dim(x, idx * k_chunk, k_chunk, ax)
        part = chunk_sum(sl(a_odd, -1), sl(a_evn, -1), sl(b_odd, 0), sl(b_evn, 0))
        return carry + part, None

    zero = jnp.zeros((*a.shape[:-1], b.shape[-1]), acc)
    out, _ = jax.lax.scan(body, zero, jnp.arange(n_chunks))
    return out


def fip_matmul(a: Array, b: Array, *, k_chunk: int = 0) -> Array:
    """Eq. (2): FIP matmul. Exactly equals a @ b (bit-exact for ints)."""
    cross = fip_cross_term(a, b, k_chunk=k_chunk)
    alpha = fip_alpha(a)
    beta = fip_beta(b)
    return cross - alpha[..., :, None] - beta


def fip_matmul_beta_folded(a: Array, b: Array, bias_folded: Array,
                           *, k_chunk: int = 0) -> Array:
    """Eq. (16): c'_ij + folded bias, where beta was pre-folded via Eq. (15).

    ``bias_folded`` must come from :func:`fold_beta_into_bias`.
    """
    cross = fip_cross_term(a, b, k_chunk=k_chunk)
    alpha = fip_alpha(a)
    return cross - alpha[..., :, None] + bias_folded


def fold_beta_into_bias(b: Array, bias: Optional[Array] = None) -> Array:
    """Eq. (15): bias_j <- bias_j - beta_j (beta precomputed after training)."""
    beta = fip_beta(b)
    if bias is None:
        return -beta
    return bias.astype(beta.dtype) - beta


# ---------------------------------------------------------------------------
# Eq. (9): y encoding (weight-column deltas), and its inverse
# ---------------------------------------------------------------------------

def make_y(b: Array) -> Array:
    """Eq. (9): y_{i,1} = b_{i,1}; y_{i,j} = b_{i,j} - b_{i,j-1} for j>1."""
    acc = _acc_dtype(b.dtype)  # deltas need one extra bit for ints (paper §4.4)
    b = b.astype(acc)
    return jnp.concatenate([b[:, :1], b[:, 1:] - b[:, :-1]], axis=1)


def y_to_b(y: Array) -> Array:
    """Inverse of :func:`make_y` — the prefix sum the FFIP pipeline performs."""
    return jnp.cumsum(y, axis=1)


# ---------------------------------------------------------------------------
# Eqs. (7)-(9): FFIP
# ---------------------------------------------------------------------------

def ffip_matmul_scan(a: Array, y: Array, *, beta: Optional[Array] = None,
                     bias_folded: Optional[Array] = None) -> Array:
    """FFIP via the literal Eqs. (7)-(9) column recurrence (dataflow-faithful).

    Emulates the free-pipeline systolic dataflow: the g terms for output
    column j are formed by adding the weight delta ``y[:, j]`` to the g terms
    of column j-1 (Eq. 8c), exactly as the FFIP PE array does in hardware.

    a: (M, K); y: (K, N) from :func:`make_y`. Supply either ``beta`` (Eq. 7)
    or ``bias_folded`` (Eq. 16) or neither (pure c' + 0 bias).
    """
    _check_even_k(a.shape[-1])
    if a.ndim != 2:
        raise ValueError("ffip_matmul_scan is the 2-D dataflow reference; "
                         "use ffip_matmul for batched operands.")
    acc = _acc_dtype(jnp.result_type(a.dtype, y.dtype))
    a = a.astype(acc)
    y = y.astype(acc)
    alpha = fip_alpha(a)

    # g init (Eqs. 8a/8b): pairwise-swapped A, before any y column is added.
    a_swapped = pair_swap(a)                      # (M, K): [a2,a1,a4,a3,...]

    def step(g, y_col):                           # g: (M, K), y_col: (K,)
        g = g + y_col[None, :]                    # Eq. (8c)
        prod = g[:, 0::2] * g[:, 1::2]            # g_{i,2k-1} * g_{i,2k}
        c_col = jnp.sum(prod, axis=-1) - alpha    # Eq. (16) form (no beta yet)
        return g, c_col

    _, cols = jax.lax.scan(step, a_swapped, y.T)  # scan over j columns
    c_prime = cols.T                              # (M, N)
    if beta is not None:
        return c_prime - beta
    if bias_folded is not None:
        return c_prime + bias_folded
    return c_prime


def pair_swap(a: Array) -> Array:
    """Swap adjacent element pairs along the last axis: [x0,x1,x2,x3] -> [x1,x0,x3,x2].

    This realizes Eqs. (8a)/(8b): g_{i,2k-1} starts from a_{i,2k} and vice versa.
    """
    _check_even_k(a.shape[-1])
    shp = a.shape
    return a.reshape(*shp[:-1], shp[-1] // 2, 2)[..., ::-1].reshape(shp)


def ffip_matmul(a: Array, b: Array, *, k_chunk: int = 0) -> Array:
    """FFIP matmul in closed form.

    Because g^{(j)}_{i,k} = a_swapped_{i,k} + b_{k,j} (prefix-summed y == b,
    proven in §3.2.1 / tests), FFIP computes the same cross term as FIP with
    the roles of the a-pair swapped. This is the vectorized (non-scan) form —
    the scan form is :func:`ffip_matmul_scan`.
    """
    cross = fip_cross_term(pair_swap(a), pair_swap_rows(b), k_chunk=k_chunk)
    alpha = fip_alpha(a)
    beta = fip_beta(b)
    return cross - alpha[..., :, None] - beta


def pair_swap_rows(b: Array) -> Array:
    """Pair-swap along axis 0 (for the B operand)."""
    _check_even_k(b.shape[0])
    k, n = b.shape
    return b.reshape(k // 2, 2, n)[:, ::-1, :].reshape(k, n)


# ---------------------------------------------------------------------------
# §3.2.1 proof replay helpers (used by tests to 'replay' the induction)
# ---------------------------------------------------------------------------

def h_terms(a: Array, b: Array, j: int) -> Array:
    """Eqs. (11)/(12): h^{(j)}_{i,k} for output column j (0-based here).

    h_{i,2k-1}^{(j)} = a_{i,2k} + b_{2k-1,j};  h_{i,2k}^{(j)} = a_{i,2k-1} + b_{2k,j}
    i.e. h^{(j)} = pair_swap(a) + b[:, j].
    """
    return pair_swap(a.astype(_acc_dtype(a.dtype))) + b[:, j][None, :].astype(
        _acc_dtype(b.dtype))


def g_terms_by_recurrence(a: Array, b: Array, j: int) -> Array:
    """g^{(j)} built strictly by the Eq. (8) recurrence (j is 0-based)."""
    y = make_y(b)
    g = pair_swap(a.astype(_acc_dtype(a.dtype)))
    for jj in range(j + 1):
        g = g + y[:, jj][None, :]
    return g


# ---------------------------------------------------------------------------
# Differentiable wrappers: FIP/FFIP forward, analytic (baseline) backward.
# The algebra is exact, so d(a@b) gradients are the correct gradients; using
# them avoids differentiating through the (M,K/2,N) intermediate.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def fip_matmul_trainable(a: Array, b: Array, k_chunk: int = 0) -> Array:
    return fip_matmul(a, b, k_chunk=k_chunk)


def _fip_fwd(a, b, k_chunk):
    return fip_matmul(a, b, k_chunk=k_chunk), (a, b)


def _fip_bwd(k_chunk, res, ct):
    a, b = res
    ga = jnp.matmul(ct, b.T.astype(ct.dtype)).astype(a.dtype)
    bt = jnp.swapaxes(a, -1, -2).astype(ct.dtype)
    gb = jnp.matmul(bt, ct)
    # collapse leading batch dims of gb into the (K, N) param grad
    while gb.ndim > 2:
        gb = gb.sum(axis=0)
    return ga, gb.astype(b.dtype)


fip_matmul_trainable.defvjp(_fip_fwd, _fip_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def ffip_matmul_trainable(a: Array, b: Array, k_chunk: int = 0) -> Array:
    return ffip_matmul(a, b, k_chunk=k_chunk)


def _ffip_fwd(a, b, k_chunk):
    return ffip_matmul(a, b, k_chunk=k_chunk), (a, b)


ffip_matmul_trainable.defvjp(_ffip_fwd, _fip_bwd)


# ---------------------------------------------------------------------------
# Arithmetic-complexity counters (Eqs. 5/6 live in core.analytical; these are
# instrumented *measured* counts used by tests to confirm the halving claim).
# ---------------------------------------------------------------------------

def count_multiplies_in_jaxpr(fn, *args) -> int:
    """Count scalar multiplies in the jaxpr of fn(*args) (dot counts M*N*K)."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    total = 0

    def visit(jx):
        nonlocal total
        for eqn in jx.eqns:
            if eqn.primitive.name == "mul":
                aval = eqn.outvars[0].aval
                # skip integer *index* arithmetic (iota*stride from slicing)
                if aval.ndim < 2 and jnp.issubdtype(aval.dtype, jnp.integer):
                    continue
                shp = aval.shape
                n = 1
                for s in shp:
                    n *= s
                total += n
            elif eqn.primitive.name in ("dot_general",):
                lhs, rhs = eqn.invars[0].aval.shape, eqn.invars[1].aval.shape
                dnums = eqn.params["dimension_numbers"]
                (lc, rc), (lb, rb) = dnums
                m = 1
                for i, s in enumerate(lhs):
                    if i not in lc and i not in lb:
                        m *= s
                n = 1
                for i, s in enumerate(rhs):
                    if i not in rc and i not in rb:
                        n *= s
                k = 1
                for i in lc:
                    k *= lhs[i]
                batch = 1
                for i in lb:
                    batch *= lhs[i]
                total += batch * m * n * k
            for sub in eqn.params.values():
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    visit(getattr(inner, "jaxpr", inner))

    visit(jaxpr.jaxpr)
    return total
