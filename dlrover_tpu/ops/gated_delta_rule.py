"""The gated delta rule in its chunk-parallel (WY) form.

Per head, with keys ``k_t`` in R^dk, values ``v_t`` in R^dv, a write
strength ``beta_t`` and a log-decay ``g_t <= 0`` (``alpha_t = exp(g_t)``),
the rule (Gated DeltaNet, Yang et al., arXiv:2412.06464) keeps a state
``S`` in R^{dv x dk}::

    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

A scan over single tokens is not a training path.  Here the sequence is
cut into chunks of ``chunk`` tokens (arXiv:2406.06484): with ``b_t`` the
running sum of ``g`` inside a chunk, ``gamma_t = exp(b_t)`` and ``S`` the
state the chunk starts from,

    A[t, i] = beta_t exp(b_t - b_i) (k_t . k_i)     for i < t, else 0
    T       = (I + A)^-1                           (unit lower triangular)
    W = T (beta gamma * K)      U = T (beta * V)
    V' = U - W S^T                                 (the chunk's writes)
    O  = (Q * gamma) S^T + M V'    M[t, i] = exp(b_t - b_i) (q_t . k_i), i <= t
    S' = gamma_C S + V'^T (K * exp(b_C - b))

Everything but ``V'`` and ``S'`` is the same work for every chunk and runs
batched over all of them; a ``lax.scan`` over the chunks carries ``S`` in
float32 through two matrix products a step.  ``T`` is built by halves
(:func:`_unit_lower_inverse`): twelve small matrix products at chunk 128
and no substitution loop.

The chunk is 128 tokens: at 2 x 8192 x 30 heads of 96 / 192 on a v5e the
rule alone, forward and backward, takes 39.9 ms against 46.3 at 64 (whole
128 x 128 MXU tiles, half as many scan steps), and a training step 2086
ms against 2187 (PERF.md §6, PR 31).

The backward of the batched part is autodiff: under a layer's remat the
chunk tensors are rebuilt once, for all chunks at a time.  The scan has a
hand-written VJP (:func:`_chunk_recurrence`): a reverse scan carrying the
state's cotangent in float32 that reads the operands and the chunk-start
states the forward emitted, where autodiff of the scan kept a float32
state a chunk (the step did not fit the chip with it: PERF.md §6, PR 31).

Matrix products take operands in the inputs' dtype and accumulate in
float32; ``g``, its running sums, ``T`` and the state are float32.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


# The inverse's float32 products: HIGH is three bfloat16 passes on the MXU
# (about 1e-5 of a product's size), half of HIGHEST's six; T is cast to the
# operands' dtype once it is built.
_INVERSE_PRECISION = jax.lax.Precision.HIGH


def _mm_f32(x, y):
    return jnp.einsum(
        "...ij,...jk->...ik", x, y, precision=_INVERSE_PRECISION
    )


@jax.custom_vjp
def _unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` for strictly lower-triangular ``a`` [..., C, C], C a
    power of two, by halves: with the inverses ``P`` of the diagonal
    blocks of size m in hand, those of size 2m are ``P - P E P``, ``E`` the
    lower-left m x m block of each (``[[L1, 0], [E, L2]]^-1 = [[P1, 0],
    [-P2 E P1, P2]]``).  From m = 1 (``P = I``) that is 2 (log2(C) - 1) batched
    C x C products, and as stable as substitution.  (The shorter product
    ``(I - a)(I + a^2)(I + a^4)...`` is not: the powers of ``a`` grow as
    C-choose-k before they vanish, and keys that resemble each other, as
    trained keys do, lost every digit in float32.)

    Its VJP is the inverse's own, ``dA = -T^T dT T^T``: two products and
    ``T`` kept, where autodiff of the halving kept every level."""
    c = a.shape[-1]
    row = jnp.arange(c)[:, None]
    col = jnp.arange(c)[None, :]
    mm = _mm_f32
    # m = 1: P = I, so the blocks of size 2 are I - E, no product
    inv = jnp.eye(c, dtype=F32) - jnp.where(
        (row // 2 == col // 2) & (row % 2 == 1) & (col % 2 == 0), a, 0.0
    )
    m = 2
    while m < c:
        lower_left = (
            (row // (2 * m) == col // (2 * m))
            & (row // m % 2 == 1) & (col // m % 2 == 0)
        )
        inv = inv - mm(mm(inv, jnp.where(lower_left, a, 0.0)), inv)
        m *= 2
    return inv


def _unit_lower_inverse_fwd(a):
    inv = _unit_lower_inverse(a)
    return inv, inv


def _unit_lower_inverse_bwd(inv, d_inv):
    inv_t = jnp.swapaxes(inv, -1, -2)
    return (-_mm_f32(_mm_f32(inv_t, d_inv), inv_t),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def gated_delta_rule(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    chunk: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    """``q, k`` [B, S, H, dk] (already normalised and scaled), ``v``
    [B, S, H, dv], ``g`` (log decay) and ``beta`` [B, S, H].  Returns the
    outputs [B, S, H, dv] in ``v``'s dtype and, under ``stop_gradient``,
    the largest ``|S|`` entry at any chunk boundary (float32 scalar).

    A sequence that is no whole number of chunks is padded with tokens
    that neither write (``beta`` 0) nor decay (``g`` 0).  ``chunk`` is a
    power of two."""
    if chunk & (chunk - 1):
        raise ValueError(f"chunk must be a power of two, got {chunk}")
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    cd = v.dtype
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (q, k, v, g, beta)
        )
    n = (s + pad) // chunk

    def chunks(a):
        """[B, S, H, ...] -> [B, H, N, C, ...]"""
        a = a.reshape(b, n, chunk, *a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    q, k, v = chunks(q), chunks(k), chunks(v)
    g, beta = chunks(g.astype(F32)), chunks(beta.astype(F32))
    run = jnp.cumsum(g, axis=-1)                          # b_t  [B,H,N,C]
    # exp(b_t - b_i) where i <= t; the other half would overflow
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(
        jnp.where(lower, run[..., :, None] - run[..., None, :], -jnp.inf)
    )
    kk = jnp.einsum("bhnck,bhnjk->bhncj", k, k, preferred_element_type=F32)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    a = jnp.where(strict, kk * decay * beta[..., None], 0.0)
    t = _unit_lower_inverse(a)
    gamma = jnp.exp(run)
    # W = T (beta gamma * K), U = T (beta * V): the scales go onto T's
    # columns, so K and V are read as they are
    w = jnp.einsum(
        "bhncj,bhnjk->bhnck",
        (t * (beta * gamma)[..., None, :]).astype(cd), k,
        preferred_element_type=F32,
    ).astype(cd)
    u = jnp.einsum(
        "bhncj,bhnjv->bhncv", (t * beta[..., None, :]).astype(cd), v,
        preferred_element_type=F32,
    ).astype(cd)
    to_end = jnp.exp(run[..., -1:] - run)                 # exp(b_C - b_i)
    k_end = (k.astype(F32) * to_end[..., None]).astype(cd)
    gamma_end = gamma[..., -1]                            # [B,H,N]

    def lead(x):
        return jnp.moveaxis(x, 2, 0)             # chunk axis first

    starts, writes, top = _chunk_recurrence(
        lead(w), lead(u), lead(k_end), lead(gamma_end)
    )
    starts, writes = jnp.moveaxis(starts, 0, 2), jnp.moveaxis(writes, 0, 2)
    qk = jnp.einsum("bhnck,bhnjk->bhncj", q, k, preferred_element_type=F32)
    o = jnp.einsum(
        "bhnck,bhnvk->bhncv",
        (q.astype(F32) * gamma[..., None]).astype(cd), starts,
        preferred_element_type=F32,
    ) + jnp.einsum(
        "bhncj,bhnjv->bhncv", (qk * decay).astype(cd), writes,
        preferred_element_type=F32,
    )
    o = jnp.moveaxis(o.astype(cd), 1, 3).reshape(b, s + pad, h, dv)
    return o[:, :s], top


def _recurrence_forward(w, u, k_end, gamma_end):
    """The scan over chunks (chunk axis first).  ``w`` [N, B, H, C, dk],
    ``u`` [N, B, H, C, dv], ``k_end`` as ``w``, ``gamma_end`` [N, B, H].
    Returns each chunk's start state [N, B, H, dv, dk] and writes
    [N, B, H, C, dv] in the operands' dtype, and the largest ``|S|``."""
    cd = u.dtype
    _, b, h, _, dk = w.shape
    dv = u.shape[-1]

    def step(carry, xs):
        state, top = carry                       # [B,H,dv,dk] f32, scalar
        w_c, u_c, k_c, decay_c = xs
        start = state.astype(cd)
        writes = (u_c - jnp.einsum(
            "bhck,bhvk->bhcv", w_c, start, preferred_element_type=F32
        )).astype(cd)
        state = state * decay_c[..., None, None] + jnp.einsum(
            "bhcv,bhck->bhvk", writes, k_c, preferred_element_type=F32
        )
        return (state, jnp.maximum(top, jnp.max(jnp.abs(state)))), (
            start, writes
        )

    (_, top), (starts, writes) = jax.lax.scan(
        step, (jnp.zeros((b, h, dv, dk), F32), jnp.zeros((), F32)),
        (w, u, k_end, gamma_end),
    )
    return starts, writes, top


@jax.custom_vjp
def _chunk_recurrence(w, u, k_end, gamma_end):
    return _recurrence_forward(w, u, k_end, gamma_end)


def _chunk_recurrence_fwd(w, u, k_end, gamma_end):
    starts, writes, top = _recurrence_forward(w, u, k_end, gamma_end)
    # what the backward reads: the operands and the two outputs, all in
    # the operands' dtype; no float32 state is kept (autodiff of the scan
    # keeps one a chunk: 0.7 GB a layer at 2 x 8192 x 30 heads)
    return (starts, writes, top), (w, k_end, gamma_end, starts, writes)


def _chunk_recurrence_bwd(res, cts):
    w, k_end, gamma_end, starts, writes = res
    d_starts, d_writes, _ = cts
    cd = w.dtype

    def step(d_state, xs):
        """``d_state``: the cotangent of the chunk's END state, float32."""
        w_c, k_c, decay_c, start, writes_c, d_start, d_writes_c = xs
        d_state_cd = d_state.astype(cd)
        d_v = d_writes_c.astype(F32) + jnp.einsum(
            "bhck,bhvk->bhcv", k_c, d_state_cd, preferred_element_type=F32
        )
        d_v_cd = d_v.astype(cd)
        d_k = jnp.einsum(
            "bhcv,bhvk->bhck", writes_c, d_state_cd,
            preferred_element_type=F32,
        )
        d_decay = jnp.sum(d_state * start.astype(F32), axis=(-2, -1))
        d_w = -jnp.einsum(
            "bhcv,bhvk->bhck", d_v_cd, start, preferred_element_type=F32
        )
        d_prev = (
            d_state * decay_c[..., None, None] + d_start.astype(F32)
            - jnp.einsum(
                "bhcv,bhck->bhvk", d_v_cd, w_c, preferred_element_type=F32
            )
        )
        return d_prev, (d_w.astype(cd), d_v_cd, d_k.astype(cd), d_decay)

    _, (d_w, d_u, d_k, d_decay) = jax.lax.scan(
        step, jnp.zeros(starts.shape[1:], F32),
        (w, k_end, gamma_end, starts, writes, d_starts, d_writes),
        reverse=True,
    )
    return d_w, d_u, d_k, d_decay


_chunk_recurrence.defvjp(_chunk_recurrence_fwd, _chunk_recurrence_bwd)
