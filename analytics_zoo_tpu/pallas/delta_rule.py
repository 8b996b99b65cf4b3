"""The gated delta rule with a per-channel decay, chunked — Pallas TPU
kernels for the pass over the chunks.

Per head, a state S in R^{dk x dv} that a token decays channel by channel,
corrects along its key and reads with its query (Kimi Delta Attention,
`keras/linear_attention.py`):

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                  g <= 0, S_0 = 0

A token-by-token loop walks that T times. The form that trains cuts the
sequence into chunks of C tokens. With G_r = g_1 + ... + g_r inside a chunk
(per channel) and S_0 the state the chunk starts from:

    A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)          j < i
    [W | U~] = (I + A)^-1 Diag(beta) [K * exp(G) | V]
    U   = U~ - W S_0
    O   = (Q * exp(G)) S_0 + P U,    P_ij = sum_c q_ic k_jc exp(G_ic - G_jc)
                                                            j <= i
    S_C = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

`chunk_prepare` computes what every chunk needs of itself, for all chunks
at once, in XLA (W, U~, Q * exp(G), K * exp(G_C - G), P, exp(G_C));
`chunk_scan` is the pass over the chunks, which is linear in the state:
the kernel `kda_chunk_fwd` keeps S^T in VMEM along a sequential chunk axis
with blocks of heads on a parallel one, `kda_chunk_bwd` walks the chunks
backwards with dS^T in VMEM (custom VJP; the state each chunk started from
is written by the forward that the backward belongs to and read again, so
nothing is solved twice). The names are what the compiler puts on the
instructions, which the benchmark's per-kernel metrics match.

**No exponent is ever positive.** Dividing by the cumulative decay
(`K / exp(G)`) overflows float32 once a chunk's decay passes e^88, which a
per-channel gate reaches inside 64 tokens. So the score-like matrices A and
P are built from sub-blocks of `_SUB` tokens: the blocks on the diagonal
pairwise, `exp(G_i - G_j)` formed for every pair i >= j and channel
(`_pair_scores`, whose gradient forms them again instead of keeping
[.., 16, 16, dk] of them), the blocks under it relative to the row block's
own boundary B (the cumulative decay just before its first token):
`(x_i exp(G_i - B)) . (k_j exp(B - G_j))`, both exponents <= 0 because j
lies before the boundary and i after it. Decay is accumulated and
exponentiated in float32; the products run in the type of q (bfloat16
under mixed precision) with float32 accumulation, the triangular solve in
float32.

Off the TPU (CPU tests) `chunk_scan` is a `lax.scan` over the chunks in
plain `jax.numpy`, or the kernels through the Pallas interpreter with
`interpret=True`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

# tokens of a sub-block of the score-like matrices: the diagonal blocks
# cost `_SUB` exponentials a token and channel, the blocks under them one
_SUB = 16
# heads a grid step of the kernels takes (the largest that divides B x H)
_HEADS_PER_STEP = (8, 4, 2, 1)
# rows of batch x heads whose chunks are prepared, scanned and
# differentiated at once (`gated_delta_rule`)
_ROWS_AT_ONCE = 8


def _lower_mask(n: int, strict: bool):
    i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return i > j if strict else i >= j


def _pair_terms(G):
    """exp(G_i - G_j) for i >= j, 0 above: [..., s, d] -> [..., s, s, d].
    The exponent is masked, not the result: above the diagonal it is
    positive and may overflow."""
    s = G.shape[-2]
    diff = G[..., :, None, :] - G[..., None, :, :]
    return jnp.exp(jnp.where(_lower_mask(s, False)[..., None], diff,
                             -jnp.inf))


@jax.custom_vjp
def _pair_scores(q, k, G):
    """The diagonal sub-blocks, pairwise: q, k, G [..., s, d] float32 ->
    (sum_c q_ic k_jc E_ijc, sum_c k_ic k_jc E_ijc), both [..., s, s] and
    zero above the diagonal, E_ijc = exp(G_ic - G_jc)."""
    E = _pair_terms(G)
    kE = k[..., None, :, :] * E
    return (jnp.sum(q[..., :, None, :] * kE, axis=-1),
            jnp.sum(k[..., :, None, :] * kE, axis=-1))


def _pair_scores_fwd(q, k, G):
    return _pair_scores(q, k, G), (q, k, G)


def _pair_scores_bwd(res, cot):
    # E is formed again: kept, it is [T, 16, dk] float32 a head
    q, k, G = res
    d_qk, d_kk = cot
    E = _pair_terms(G)
    d_q = jnp.sum(d_qk[..., None] * k[..., None, :, :] * E, axis=-2)
    d_k_row = jnp.sum(d_kk[..., None] * k[..., None, :, :] * E, axis=-2)
    d_k_col = jnp.sum((d_qk[..., None] * q[..., :, None, :]
                       + d_kk[..., None] * k[..., :, None, :]) * E, axis=-3)
    # E_ij moves with G_i as the row's terms do and against G_j
    d_G = q * d_q + k * d_k_row - k * d_k_col
    return d_q, d_k_row + d_k_col, d_G


_pair_scores.defvjp(_pair_scores_fwd, _pair_scores_bwd)


def _score_matrices(q, k, G, cdt):
    """(P, KK) [N, n, C, C] float32 of one chunk each, lower triangles
    (P with its diagonal, KK too: its caller masks it): q, k, G
    [N, n, C, d] float32. Sub-blocks as the module docstring has them."""
    N, n, C, d = q.shape
    sub = _SUB if C % _SUB == 0 else C
    blocks = (N, n, C // sub, sub, d)
    qk_d, kk_d = _pair_scores(q.reshape(blocks), k.reshape(blocks),
                              G.reshape(blocks))
    rows_qk, rows_kk = [], []
    for a in range(C // sub):
        lo, hi = a * sub, (a + 1) * sub
        parts_qk, parts_kk = [qk_d[:, :, a]], [kk_d[:, :, a]]
        if a:
            bound = G[:, :, lo - 1:lo]                 # just before row lo
            left = jnp.exp(G[:, :, lo:hi] - bound)
            right = (k[:, :, :lo] * jnp.exp(bound - G[:, :, :lo])).astype(cdt)
            for x, parts in ((q, parts_qk), (k, parts_kk)):
                parts.insert(0, jnp.einsum(
                    "ncid,ncjd->ncij", (x[:, :, lo:hi] * left).astype(cdt),
                    right, preferred_element_type=jnp.float32))
        if hi < C:
            zeros = jnp.zeros((N, n, sub, C - hi), jnp.float32)
            parts_qk.append(zeros)
            parts_kk.append(zeros)
        rows_qk.append(jnp.concatenate(parts_qk, axis=-1))
        rows_kk.append(jnp.concatenate(parts_kk, axis=-1))
    return (jnp.concatenate(rows_qk, axis=-2),
            jnp.concatenate(rows_kk, axis=-2))


def chunk_prepare(q, k, v, g, beta, chunk: int):
    """What every chunk needs of itself, all chunks at once. q, k
    [N, T, dk], v [N, T, dv] (N = batch x heads, T a multiple of `chunk`),
    g [N, T, dk] float32 log-decay (<= 0), beta [N, T]. Returns
    (W [N, T, dk], U~ [N, T, dv], Q exp(G) [N, T, dk], K exp(G_C - G)
    [N, T, dk], P [N, T, chunk]) in q's type and exp(G_C) [N, T / chunk,
    dk] float32: `chunk_scan`'s arguments."""
    N, T, dk = q.shape
    n, f32, cdt = T // chunk, jnp.float32, q.dtype

    def chunks(a):
        return a.astype(f32).reshape(N, n, chunk, -1)

    q, k, v, beta = chunks(q), chunks(k), chunks(v), chunks(beta)
    G = jnp.cumsum(chunks(g), axis=2)
    p, kk = _score_matrices(q, k, G, cdt)
    a = jnp.where(_lower_mask(chunk, True), beta * kk, 0.0)
    solved = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(chunk, dtype=f32),
        beta * jnp.concatenate([k * jnp.exp(G), v], axis=-1),
        lower=True, unit_diagonal=True)
    last = G[:, :, -1:]
    flat = (N, T, -1)
    return (solved[..., :dk].astype(cdt).reshape(flat),
            solved[..., dk:].astype(cdt).reshape(flat),
            (q * jnp.exp(G)).astype(cdt).reshape(flat),
            (k * jnp.exp(last - G)).astype(cdt).reshape(flat),
            p.astype(cdt).reshape(flat), jnp.exp(last[:, :, 0]))


# ---------------------------------------------------------------- the scan

def _scan_plain(w, ut, qg, kd, p, decay):
    """`chunk_scan` in plain jax.numpy: a `lax.scan` over the chunks."""
    N, T, dk = w.shape
    n, f32 = decay.shape[1], jnp.float32
    C = T // n

    def per_chunk(a):                       # [N, T, x] -> [n, N, C, x]
        return a.reshape(N, n, C, -1).transpose(1, 0, 2, 3)

    def step(s, xs):                        # s: S^T [N, dv, dk] float32
        w_c, ut_c, qg_c, kd_c, p_c, decay_c = xs
        s_lo = s.astype(w.dtype)
        u = ut_c.astype(f32) - jnp.einsum(
            "nck,nvk->ncv", w_c, s_lo, preferred_element_type=f32)
        u_lo = u.astype(w.dtype)
        o = jnp.einsum("nck,nvk->ncv", qg_c, s_lo,
                       preferred_element_type=f32) \
            + jnp.einsum("ncj,njv->ncv", p_c, u_lo,
                         preferred_element_type=f32)
        s = s * decay_c[:, None, :] + jnp.einsum(
            "ncv,nck->nvk", u_lo, kd_c, preferred_element_type=f32)
        return s, o.astype(w.dtype)

    s0 = jnp.zeros((N, ut.shape[-1], dk), f32)
    _, o = jax.lax.scan(step, s0, (per_chunk(w), per_chunk(ut),
                                   per_chunk(qg), per_chunk(kd),
                                   per_chunk(p), decay.transpose(1, 0, 2)))
    return o.transpose(1, 0, 2, 3).reshape(N, T, -1)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _fwd_kernel(heads, w_ref, ut_ref, qg_ref, kd_ref, p_ref, decay_ref,
                o_ref, *rest):
    """One chunk of `heads` heads. `rest` = (states_ref,) st_sc: the state
    S^T [dv, dk] float32 a head, carried along the chunk axis; the state
    each chunk starts from is written out where a backward will want it."""
    from jax.experimental import pallas as pl

    states_ref, st_sc = rest if len(rest) == 2 else (None, rest[0])

    @pl.when(pl.program_id(1) == 0)
    def _first_chunk():
        st_sc[...] = jnp.zeros_like(st_sc)

    cdt = w_ref.dtype
    for h in range(heads):
        st = st_sc[h]
        s_lo = st.astype(cdt)
        if states_ref is not None:
            states_ref[h, 0] = s_lo
        u = ut_ref[h].astype(jnp.float32) - _dot(w_ref[h], s_lo, _NT)
        u_lo = u.astype(cdt)
        o_ref[h] = (_dot(qg_ref[h], s_lo, _NT)
                    + _dot(p_ref[h], u_lo, _NN)).astype(o_ref.dtype)
        st_sc[h] = st * decay_ref[h, 0] + _dot(u_lo, kd_ref[h], _TN)


def _bwd_kernel(heads, w_ref, ut_ref, qg_ref, kd_ref, p_ref, decay_ref,
                states_ref, do_ref, dw_ref, dut_ref, dqg_ref, dkd_ref, dp_ref,
                ddecay_ref, dst_sc):
    """One chunk of `heads` heads, the chunks walked backwards: dst_sc is
    the gradient of the state the chunk ENDS with, S_C^T, and leaves as
    that of the state it started from."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _last_chunk():
        dst_sc[...] = jnp.zeros_like(dst_sc)

    cdt = w_ref.dtype
    for h in range(heads):
        s_lo, ds = states_ref[h, 0], dst_sc[h]
        ds_lo, do = ds.astype(cdt), do_ref[h]
        w, qg, kd, decay = w_ref[h], qg_ref[h], kd_ref[h], decay_ref[h, 0]
        u_lo = (ut_ref[h].astype(jnp.float32)
                - _dot(w, s_lo, _NT)).astype(cdt)
        du = _dot(p_ref[h], do, _TN) + _dot(kd, ds_lo, _NT)
        du_lo = du.astype(cdt)
        dut_ref[h] = du_lo
        dw_ref[h] = (-_dot(du_lo, s_lo, _NN)).astype(cdt)
        dqg_ref[h] = _dot(do, s_lo, _NN).astype(cdt)
        dkd_ref[h] = _dot(u_lo, ds_lo, _NN).astype(cdt)
        dp_ref[h] = _dot(do, u_lo, _NT).astype(cdt)
        ddecay_ref[h, 0] = jnp.sum(ds * s_lo.astype(jnp.float32), axis=0,
                                   keepdims=True)
        dst_sc[h] = ds * decay + _dot(do, qg, _TN) - _dot(du_lo, w, _TN)


def _heads_per_step(n: int) -> int:
    return next(h for h in _HEADS_PER_STEP if n % h == 0)


def _specs(heads, C, dk, dv, at):
    """Block specs of the six operands of a chunk; `at(c)` is the chunk a
    grid step reads."""
    from jax.experimental import pallas as pl

    def rows(width):
        return pl.BlockSpec((heads, C, width), lambda i, c: (i, at(c), 0))

    return [rows(dk), rows(dv), rows(dk), rows(dk), rows(C),
            pl.BlockSpec((heads, 1, 1, dk), lambda i, c: (i, at(c), 0, 0))]


def _scan_cost(N, T, C, dk, dv, state_products, chunk_products, arrays,
               itemsize):
    """A token's products against the state (dk x dv each) and inside its
    chunk (C x dv each), and the [N, T, d] arrays moved."""
    from jax.experimental import pallas as pl
    return pl.CostEstimate(
        flops=2 * N * T * dv * (state_products * dk + chunk_products * C),
        transcendentals=0,
        bytes_accessed=N * T * arrays * max(dk, dv) * itemsize)


def _kernel_fwd(w, ut, qg, kd, p, decay, interpret, keep_states):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, T, dk = w.shape
    dv, n = ut.shape[-1], decay.shape[1]
    C, heads = T // n, _heads_per_step(N)
    out_shape = [jax.ShapeDtypeStruct((N, T, dv), w.dtype)]
    out_specs = [pl.BlockSpec((heads, C, dv), lambda i, c: (i, c, 0))]
    if keep_states:
        out_shape.append(jax.ShapeDtypeStruct((N, n, dv, dk), w.dtype))
        out_specs.append(pl.BlockSpec((heads, 1, dv, dk),
                                      lambda i, c: (i, c, 0, 0)))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads),
        out_shape=out_shape,
        grid=(N // heads, n),
        in_specs=_specs(heads, C, dk, dv, lambda c: c),
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=_scan_cost(N, T, C, dk, dv, 3, 1, 6 + 2 * keep_states,
                                 w.dtype.itemsize),
        interpret=interpret,
        name="kda_chunk_fwd",
    )(w, ut, qg, kd, p, decay[:, :, None, :])


def _kernel_bwd(w, ut, qg, kd, p, decay, states, do, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, T, dk = w.shape
    dv, n = ut.shape[-1], decay.shape[1]
    C, heads = T // n, _heads_per_step(N)

    def back(c):
        return n - 1 - c

    specs = _specs(heads, C, dk, dv, back)
    *grads, d_decay = pl.pallas_call(
        functools.partial(_bwd_kernel, heads),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype)
                   for a in (w, ut, qg, kd, p)]
        + [jax.ShapeDtypeStruct((N, n, 1, dk), jnp.float32)],
        grid=(N // heads, n),
        in_specs=specs + [
            pl.BlockSpec((heads, 1, dv, dk),
                         lambda i, c: (i, back(c), 0, 0)),
            pl.BlockSpec((heads, C, dv), lambda i, c: (i, back(c), 0))],
        out_specs=specs,
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=_scan_cost(N, T, C, dk, dv, 7, 2, 14, w.dtype.itemsize),
        interpret=interpret,
        name="kda_chunk_bwd",
    )(w, ut, qg, kd, p, decay[:, :, None, :], states, do)
    return (*grads, d_decay[:, :, 0, :])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan_kernels(w, ut, qg, kd, p, decay, interpret):
    return _kernel_fwd(w, ut, qg, kd, p, decay, interpret, False)[0]


def _scan_kernels_fwd(w, ut, qg, kd, p, decay, interpret):
    o, states = _kernel_fwd(w, ut, qg, kd, p, decay, interpret, True)
    return o, (w, ut, qg, kd, p, decay, states)


def _scan_kernels_bwd(interpret, res, do):
    return _kernel_bwd(*res, do.astype(res[0].dtype), interpret)


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def chunk_scan(w, ut, qg, kd, p, decay, interpret: Optional[bool] = None):
    """The pass over the chunks: `chunk_prepare`'s results -> O [N, T, dv]
    in their type, from S_0 = 0. Differentiable in all six."""
    if not (interpret or jax.default_backend() == "tpu"):
        return _scan_plain(w, ut, qg, kd, p, decay)
    return _scan_kernels(w, ut, qg, kd, p, decay, bool(interpret))


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64,
                     interpret: Optional[bool] = None):
    """o_t = S_t^T q_t of the recurrence in the module docstring, chunked:
    q, k [N, T, dk], v [N, T, dv], g [N, T, dk] float32 (<= 0), beta
    [N, T] -> [N, T, dv] in q's type. Any T: the sequence is padded to a
    multiple of `chunk` with tokens that change no state (k = 0, beta = 0,
    g = 0). Differentiable in all five.

    More than `_ROWS_AT_ONCE` rows of N are taken that many at a time
    (`lax.map`), each under a `jax.checkpoint`: what `chunk_prepare` keeps
    for its gradient (a dozen float32 [rows, T, dk] arrays) is then held
    for one group of heads at a time and computed again in the backward
    pass, the chunk kernel's forward with it."""
    N, T = q.shape[:2]
    pad = (-T) % chunk
    operands = (q, k, v, g, beta)
    if pad:
        operands = tuple(jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (a.ndim - 2)) for a in operands)

    def core(rows):
        with jax.named_scope("kda/chunk_prepare"):
            prepared = chunk_prepare(*rows, chunk)
        with jax.named_scope("kda/chunk_scan"):
            return chunk_scan(*prepared, interpret=interpret)

    if N > _ROWS_AT_ONCE and N % _ROWS_AT_ONCE == 0:
        o = jax.lax.map(jax.checkpoint(core), tuple(
            a.reshape((N // _ROWS_AT_ONCE, _ROWS_AT_ONCE) + a.shape[1:])
            for a in operands))
        o = o.reshape((N,) + o.shape[2:])
    else:
        o = core(operands)
    return o[:, :T] if pad else o


def recurrent_delta_rule(q, k, v, g, beta):
    """The same, token by token in float32: what the chunked form is
    tested against. A `lax.scan` of T steps; not a training path."""
    f32 = jnp.float32

    def step(s, x):                                 # s [N, dk, dv]
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None]
        u = b_t[:, None] * (v_t - jnp.einsum("nk,nkv->nv", k_t, s))
        s = s + k_t[..., None] * u[:, None, :]
        return s, jnp.einsum("nk,nkv->nv", q_t, s)

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta))
    s0 = jnp.zeros((q.shape[0], q.shape[-1], v.shape[-1]), f32)
    with jax.default_matmul_precision("highest"):
        return jnp.moveaxis(jax.lax.scan(step, s0, xs)[1], 0, 1)
