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

What every chunk needs of itself (W, U~, Q * exp(G), K * exp(G_C - G), P,
exp(G_C)) depends on no other chunk. On the TPU two kernels compute it
with the chunk in VMEM, 8 heads of one chunk a grid step, every step its
own: `delta_prepare_fwd`, and `delta_prepare_bwd` for the gradient (custom
VJP), which keeps nothing but q, k, v, g, beta and walks the way to the six
results again before it walks back; no [.., C, C] matrix, no float32 copy
of q, k, v and no exponential ever reaches HBM. `chunk_prepare` is the
same in XLA, all chunks at once: the path off the TPU and for shapes the
kernels do not take (`_prepare_fits`: a chunk that is a power of two of 16
or more, channels in whole lane tiles), and what the tests hold the
kernels to. `chunk_scan` is the pass over the chunks, which is linear in
the state: the kernel `kda_chunk_fwd` keeps S^T in VMEM along a sequential
chunk axis with blocks of heads on a parallel one, `kda_chunk_bwd` walks
the chunks backwards with dS^T in VMEM (custom VJP; the state each chunk
started from is written by the forward that the backward belongs to and
read again, so nothing is solved twice). The four names are what the
compiler puts on the instructions, and the benchmark's per-kernel metrics
match them: `kda_[a-z_]+` the scan's (`kda_time_share`, `kda_roofline`,
whose work counts the scan alone), `delta_prepare_(fwd|bwd)` the
preparation's (`kda_prepare_time_share`): a preparation kernel must not be
named `kda_`.

**No exponent is ever positive.** Dividing by the cumulative decay
(`K / exp(G)`) overflows float32 once a chunk's decay passes e^88, which a
per-channel gate reaches inside 64 tokens. So a score between row i and
column j < i is always taken relative to a boundary B between them,
`(x_i exp(G_i - B)) . (k_j exp(B - G_j))`, both exponents <= 0.
`chunk_prepare` does so between sub-blocks of `_SUB` tokens (B the
cumulative decay just before the row block's first token) and forms the
blocks on the diagonal pairwise, `exp(G_i - G_j)` for every pair i >= j
and channel (`_pair_scores`, whose gradient forms them again instead of
keeping [.., 16, 16, dk] of them). The kernels apply the boundary rule all
the way down: the chunk is halved, each half again, to single tokens
(`_halves`); at the level of half length h a token of a lower half is
scaled by the decay from its half's first token to itself and a token of
an upper half by the decay after it to its half's end (`_level_decays`:
sums of g's own terms, found half by half with one shift a level), and ONE
masked [C, dk] x [dk, C] product on the MXU gives the level's part of A
and P for all blocks of the chunk: log2(C) exponentials a token and
channel where the pairwise form takes `_SUB`, and no reduction over
lanes. Decay is accumulated and exponentiated in float32; the products
between sub-blocks run in the type of q (bfloat16 under mixed precision)
with float32 accumulation, those inside a sub-block and the triangular
solve in float32 (on the MXU: `_dot32`, several bfloat16 passes). The
kernels solve by the same halving: the inverse of I + A on the blocks of
2h from that on the blocks of h and the level's own part of A, two
products a level, then one product with Diag(beta) [K exp(G) | V]; the
gradient of the solve is that inverse transposed,
R = (I + A)^-T [dW | dU~], dA = -R [W | U~]^T under the diagonal.

Off the TPU (CPU tests) `chunk_prepare` prepares and `chunk_scan` is a
`lax.scan` over the chunks in plain `jax.numpy`; `interpret=True` runs all
four kernels through the Pallas interpreter.
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
# of those, the heads whose preparation is written level by level side by
# side, so that one head's products fill another's waits
_HEADS_ABREAST = 4
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


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _heads_per_step(n: int) -> int:
    return next(h for h in _HEADS_PER_STEP if n % h == 0)


# ------------------------------------------- the preparation as kernels

def _takes_kernels(interpret) -> bool:
    return bool(interpret) or jax.default_backend() == "tpu"


def _prepare_fits(chunk: int, dk: int, dv: int, interpret) -> bool:
    """Whether `delta_prepare_fwd` / `delta_prepare_bwd` take a chunk:
    halves down to single tokens want a power of two, the products in
    q's type between sub-blocks want `_SUB` tokens or more, and the
    compiler wants whole lane tiles of the channels."""
    return (chunk >= _SUB and chunk & (chunk - 1) == 0
            and (bool(interpret) or (dk % 128 == 0 and dv % 128 == 0)))


def _dot32(a, b, contract):
    """A float32 product on the MXU: several passes, float32 to the last
    bit the unit keeps."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _halves(C: int, width: int):
    """The halving of a chunk of C tokens (a power of two), finest first:
    for every half length h = 1, 2, .. C / 2 the tuple (h, low [C, width]:
    token t lies in the lower half of its block of 2h, pair [C, C]: row i
    lies in the lower half and column j in the upper half of ONE block of
    2h). Every pair j < i is in exactly one level's `pair`. Last, the
    diagonal [C, C]."""
    t = jax.lax.broadcasted_iota(jnp.int32, (C, width), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    differ = i ^ j                  # its highest bit: the level of (i, j)
    levels = []
    for b in range(C.bit_length() - 1):
        h = 1 << b
        levels.append((h, (t & h) != 0,
                       (differ >= h) & (differ < 2 * h) & ((i & h) != 0)))
    return levels, i == j


def _level_decays(g, levels):
    """g [C, dk] float32 (<= 0) -> (the exponent of every level [C, dk],
    G, G_C - G), all sums of g's own terms and so never positive. A
    level's exponent is, for a token of a lower half, the decay from its
    half's first token to itself, and for a token of an upper half the
    decay after it to its half's end: a row against a column of one block
    multiplies the two into exp(G_i - G_j). Half by half: what a half of
    length h knows of itself (`inc`, the sum from its first token; `rev`,
    the sum after the token to its end) gives the half of 2h by one shift
    of h tokens."""
    from jax.experimental.pallas import tpu as pltpu

    C = g.shape[0]
    inc, rev, exponents = g, jnp.zeros_like(g), []
    for h, low, _ in levels:
        exponents.append(jnp.where(low, inc, rev))
        whole = inc + rev                       # one number a half
        inc = inc + jnp.where(low, pltpu.roll(whole, h, 0), 0.0)
        rev = rev + jnp.where(low, 0.0, pltpu.roll(whole, C - h, 0))
    return exponents, inc, rev


def _level_decays_transposed(d_exponents, d_inc, d_rev, levels):
    """The transpose of `_level_decays`: cotangents of its results -> dg."""
    from jax.experimental.pallas import tpu as pltpu

    C = d_inc.shape[0]
    for (h, low, _), d_e in reversed(list(zip(levels, d_exponents))):
        d_whole = pltpu.roll(jnp.where(low, d_inc, 0.0), C - h, 0) \
            + pltpu.roll(jnp.where(low, 0.0, d_rev), h, 0)
        d_inc = d_inc + d_whole + jnp.where(low, d_e, 0.0)
        d_rev = d_rev + d_whole + jnp.where(low, 0.0, d_e)
    return d_inc


def _column(row, eye):                      # [1, C] -> [C, 1]
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _chunk_scores(q, k, v, g, beta, levels, eye):
    """One chunk of one head in VMEM, as far as the solve: q, k [C, dk], v
    [C, dv] in the step's type, g [C, dk] float32, beta [C, 1] float32 ->
    P, KK under the diagonal, every level's own part of A = Diag(beta) KK,
    the decayed copies in float32 and what a gradient wants of the way
    there."""
    f32, cdt, C = jnp.float32, q.dtype, q.shape[0]
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    exponents, G, G_rest = _level_decays(g, levels)
    p = jnp.where(eye, jnp.sum(qf * kf, axis=1, keepdims=True), 0.0)
    kk, a_levels, scaled = jnp.zeros((C, C), f32), [], []
    for (h, _, pair), exponent in zip(levels, exponents):
        e = jnp.exp(exponent)
        qe, ke = qf * e, kf * e
        rows = jnp.concatenate([qe, ke], axis=0)
        if h < _SUB:                # inside a sub-block: float32 products
            both = _dot32(rows, ke, _NT)
        else:                       # between sub-blocks: q's type
            both = _dot(rows.astype(cdt), ke.astype(cdt), _NT)
        p = p + jnp.where(pair, both[:C], 0.0)
        kk_h = jnp.where(pair, both[C:], 0.0)
        kk = kk + kk_h
        a_levels.append(beta * kk_h)
        scaled.append((e, qe, ke))
    eg, eg_rest = jnp.exp(G), jnp.exp(G_rest)
    return dict(cdt=cdt, qf=qf, kf=kf, vf=vf, beta=beta, p=p, kk=kk,
                a=a_levels, scaled=scaled, eg=eg, eg_rest=eg_rest,
                kg=kf * eg, qg=qf * eg, kd=kf * eg_rest)


def _inverses(a_levels_by_head, eye):
    """(I + A)^-1 [C, C] float32 of every head's chunk, from the levels'
    parts of A: the inverse on the blocks of 2h from that on the blocks
    of h, [[X, 0], [Z, Y]]^-1 = [[X^-1, 0], [-Y^-1 Z X^-1, Y^-1]]. Level
    by level for all heads, because a level's two products wait for each
    other and another head's do not."""
    inverses = [eye.astype(jnp.float32) - a[0] for a in a_levels_by_head]
    for level in range(1, len(a_levels_by_head[0])):
        left = [_dot32(t, a[level], _NN)
                for t, a in zip(inverses, a_levels_by_head)]
        inverses = [t - _dot32(u, t, _NN) for t, u in zip(inverses, left)]
    return inverses


def _prepare_chunks(refs, first, abreast, levels, eye):
    """`abreast` heads from `first` on of the five operand refs: each
    one's `_chunk_scores` with its inverse and W, U~ float32 added."""
    chunks = [_chunk_scores(*(ref[first + r] for ref in refs[:4]),
                            _column(refs[4][first + r, 0], eye), levels, eye)
              for r in range(abreast)]
    for c, inverse in zip(chunks, _inverses([c["a"] for c in chunks], eye)):
        c.update(inverse=inverse,
                 w=_dot32(inverse, c["beta"] * c["kg"], _NN),
                 ut=_dot32(inverse, c["beta"] * c["vf"], _NN))
    return chunks


def _heads_abreast(heads: int) -> int:
    return next(a for a in (_HEADS_ABREAST, 2, 1) if heads % a == 0)


def _prepare_fwd_kernel(heads, q_ref, k_ref, v_ref, g_ref, beta_ref, w_ref,
                        ut_ref, qg_ref, kd_ref, p_ref, decay_ref):
    """One chunk of `heads` heads."""
    C, dk = q_ref.shape[1:]
    levels, eye = _halves(C, dk)
    abreast = _heads_abreast(heads)

    def group(i, carry):
        first = i * abreast
        chunks = _prepare_chunks((q_ref, k_ref, v_ref, g_ref, beta_ref),
                                 first, abreast, levels, eye)
        for r, c in enumerate(chunks):
            for ref, name in ((w_ref, "w"), (ut_ref, "ut"), (qg_ref, "qg"),
                              (kd_ref, "kd"), (p_ref, "p")):
                ref[first + r] = c[name].astype(ref.dtype)
            decay_ref[first + r, 0] = c["eg"][C - 1:]
        return carry

    jax.lax.fori_loop(0, heads // abreast, group, None)


def _solve_gradient(c, d_w, d_ut):
    """The solve's gradient is the same inverse transposed:
    R = (I + A)^-T [dW | dU~], dA = -R [W | U~]^T (its caller's masks keep
    what lies under the diagonal)."""
    r_w = _dot32(c["inverse"], d_w, _TN)
    r_u = _dot32(c["inverse"], d_ut, _TN)
    return r_w, r_u, -(_dot32(r_w, c["w"], _NT) + _dot32(r_u, c["ut"], _NT))


def _chunk_gradient(c, solved, cotangents, levels, eye, last):
    """One chunk of one head, back along the way `_prepare_chunks` went: c
    its results, `solved` its `_solve_gradient`, `cotangents` those of
    Q exp(G), K exp(G_C - G), P (float32) and exp(G_C) [1, dk] -> dq, dk,
    dv, dg [C, .] and dbeta [C, 1], float32."""
    (r_w, r_u, d_a), (d_qg, d_kd, d_p, d_decay) = solved, cotangents
    C, cdt = d_p.shape[0], c["cdt"]
    beta, eg, kg = c["beta"], c["eg"], c["kg"]
    d_beta = jnp.sum(r_w * kg, axis=1, keepdims=True) \
        + jnp.sum(r_u * c["vf"], axis=1, keepdims=True) \
        + jnp.sum(d_a * c["kk"], axis=1, keepdims=True)
    d_kg, d_kk = beta * r_w, beta * d_a
    on_diagonal = jnp.sum(jnp.where(eye, d_p, 0.0), axis=1, keepdims=True)
    d_q = d_qg * eg + on_diagonal * c["kf"]
    d_k = d_kg * eg + d_kd * c["eg_rest"] + on_diagonal * c["qf"]
    d_G = d_qg * c["qg"] + d_kg * kg + jnp.where(last, d_decay * eg, 0.0)
    d_exponents = []
    for (h, _, pair), (e, qe, ke) in zip(levels, c["scaled"]):
        both = jnp.concatenate([jnp.where(pair, d_p, 0.0),
                                jnp.where(pair, d_kk, 0.0)], axis=0)
        rows = jnp.concatenate([qe, ke], axis=0)
        if h < _SUB:
            by_rows = _dot32(both, ke, _NN)
            by_columns = _dot32(both, rows, _TN)
        else:
            both = both.astype(cdt)
            by_rows = _dot(both, ke.astype(cdt), _NN)
            by_columns = _dot(both, rows.astype(cdt), _TN)
        d_qe, d_ke = by_rows[:C], by_rows[C:] + by_columns
        d_q, d_k = d_q + d_qe * e, d_k + d_ke * e
        d_exponents.append(d_qe * qe + d_ke * ke)
    d_g = _level_decays_transposed(d_exponents, d_G, d_kd * c["kd"], levels)
    return d_q, d_k, beta * r_u, d_g, d_beta


def _prepare_bwd_kernel(heads, q_ref, k_ref, v_ref, g_ref, beta_ref, dw_ref,
                        dut_ref, dqg_ref, dkd_ref, dp_ref, ddecay_ref,
                        dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref):
    """One chunk of `heads` heads: the way to the six results again, then
    back along it."""
    f32 = jnp.float32
    C, dk = q_ref.shape[1:]
    levels, eye = _halves(C, dk)
    last = jax.lax.broadcasted_iota(jnp.int32, (C, dk), 0) == C - 1
    abreast = _heads_abreast(heads)

    def group(i, carry):
        first = i * abreast
        chunks = _prepare_chunks((q_ref, k_ref, v_ref, g_ref, beta_ref),
                                 first, abreast, levels, eye)
        solved = [_solve_gradient(c, dw_ref[first + r].astype(f32),
                                  dut_ref[first + r].astype(f32))
                  for r, c in enumerate(chunks)]
        for r, c in enumerate(chunks):
            cotangents = [ref[first + r].astype(f32) for ref in (
                dqg_ref, dkd_ref, dp_ref)] + [ddecay_ref[first + r, 0]]
            *rows, d_beta = _chunk_gradient(c, solved[r], cotangents, levels,
                                            eye, last)
            for ref, a in zip((dq_ref, dk_ref, dv_ref, dg_ref), rows):
                ref[first + r] = a.astype(ref.dtype)
            dbeta_ref[first + r, 0] = jnp.sum(jnp.where(eye, d_beta, 0.0),
                                              axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, heads // abreast, group, None)


def _prepare_specs(heads, C, dk, dv):
    """Block specs of a chunk of q, k, v, g, beta [N, n, 1, C] and of the
    six results (exp(G_C) as [N, n, 1, dk])."""
    from jax.experimental import pallas as pl

    def rows(width):
        return pl.BlockSpec((heads, C, width), lambda i, c: (i, c, 0))

    def row(width):
        return pl.BlockSpec((heads, 1, 1, width), lambda i, c: (i, c, 0, 0))

    return [rows(dk), rows(dk), rows(dv), rows(dk), row(C)], \
        [rows(dk), rows(dv), rows(dk), rows(dk), rows(C), row(dk)]


def _prepare_call(kernel, name, operands, out_shape, chunk, interpret):
    """`pl.pallas_call` of one of the two kernels: a grid of groups of
    heads x chunks, every step its own. Operands q, k, v, g, beta
    [N, n, 1, chunk] and, for the gradient, the six cotangents."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, v = operands[0], operands[2]
    (N, T, dk), dv = q.shape, v.shape[-1]
    heads, halves = _heads_per_step(N), chunk.bit_length() - 1
    ins, outs = _prepare_specs(heads, chunk, dk, dv)
    backward = len(operands) > len(ins)
    # the way to the six results is walked once forward and about three
    # times over for the gradient
    passes = 3 if backward else 1
    return pl.pallas_call(
        functools.partial(kernel, heads),
        out_shape=out_shape,
        grid=(N // heads, T // chunk),
        in_specs=ins + outs if backward else ins,
        out_specs=ins if backward else outs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=passes * 2 * N * T * chunk * (
                2 * halves * dk + 2 * halves * chunk + dk + dv),
            transcendentals=passes * N * T * dk * (halves + 2),
            bytes_accessed=sum(a.size * a.dtype.itemsize for a in operands)
            + sum(s.size * s.dtype.itemsize for s in out_shape)),
        interpret=interpret,
        name=name,
    )(*operands)


def _prepare_kernel_fwd(q, k, v, g, beta, chunk, interpret):
    (N, T, dk), dv, n = q.shape, v.shape[-1], q.shape[1] // chunk
    *rows, decay = _prepare_call(
        _prepare_fwd_kernel, "delta_prepare_fwd",
        (q, k, v, g, beta.reshape(N, n, 1, chunk)),
        [jax.ShapeDtypeStruct((N, T, w), q.dtype)
         for w in (dk, dv, dk, dk, chunk)]
        + [jax.ShapeDtypeStruct((N, n, 1, dk), jnp.float32)],
        chunk, interpret)
    return (*rows, decay[:, :, 0, :])


def _prepare_kernel_bwd(q, k, v, g, beta, cotangents, chunk, interpret):
    (N, T, _), n = q.shape, q.shape[1] // chunk
    *d_rows, d_decay = cotangents
    *grads, d_beta = _prepare_call(
        _prepare_bwd_kernel, "delta_prepare_bwd",
        (q, k, v, g, beta.reshape(N, n, 1, chunk), *d_rows,
         d_decay[:, :, None, :]),
        [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (q, k, v, g)]
        + [jax.ShapeDtypeStruct((N, n, 1, chunk), jnp.float32)],
        chunk, interpret)
    return (*grads, d_beta.reshape(N, T))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _prepare_kernels(q, k, v, g, beta, chunk, interpret):
    return _prepare_kernel_fwd(q, k, v, g, beta, chunk, interpret)


def _prepare_kernels_fwd(q, k, v, g, beta, chunk, interpret):
    # nothing but the operands is kept: the gradient walks the way again
    return _prepare_kernel_fwd(q, k, v, g, beta, chunk, interpret), \
        (q, k, v, g, beta)


def _prepare_kernels_bwd(chunk, interpret, res, cotangents):
    return _prepare_kernel_bwd(*res, cotangents, chunk, interpret)


_prepare_kernels.defvjp(_prepare_kernels_fwd, _prepare_kernels_bwd)


def _prepare(q, k, v, g, beta, chunk: int, interpret):
    """`chunk_prepare` by the kernels where `chunk_scan` takes its own and
    the shapes fit them, in XLA everywhere else."""
    if _takes_kernels(interpret) and _prepare_fits(
            chunk, q.shape[-1], v.shape[-1], interpret):
        return _prepare_kernels(q, k, v, g.astype(jnp.float32),
                                beta.astype(jnp.float32), chunk,
                                bool(interpret))
    return chunk_prepare(q, k, v, g, beta, chunk)


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
    if not _takes_kernels(interpret):
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
            prepared = _prepare(*rows, chunk, interpret)
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
