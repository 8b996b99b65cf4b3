"""Sparse embedding-gradient path: segment-sum + fused row-wise Adam
(ISSUE 9 tentpole, part 2).

`learn/lazy_embedding.py` already updates only the touched rows, but it
measured SLOWER than the dense sweep at MovieLens density because (a)
the gradient w.r.t. a [vocab, dim] table still MATERIALIZES densely
(the gather's VJP is zeros + scatter-add: two full-table passes) and
(b) XLA's large-table `.at[].set` scatter is not in-place (full-table
copies per update). This module removes both:

- **No dense gradients.** The fused one-step gathers each table's
  batch rows OUTSIDE the differentiated function, rewrites the batch's
  id column to `arange(B)` (`LazyEmbeddingSpec.set_ids_fn`), and places
  the [B, dim] rows array at the table's leaf. The model's own gather
  then reads `rows[0..B)` — identical forward values — and the
  backward produces a [B, dim] per-example row-gradient. A
  vocab-sized cotangent never exists.
- **Segment-sum.** Duplicate ids inside the batch are merged by
  sort + neighbor-compare (static shapes): slot j of the compacted
  output holds the j-th unique id and the SUM of its entries' row
  grads — exactly the scatter-add the dense VJP would have done,
  over B rows instead of the vocabulary.
- **Fused gather→Adam→scatter kernel.** One Pallas kernel walks the
  B slots; a scalar-prefetch index map DMAs exactly the touched
  (param, m, v) rows in and the updated rows out, in place via
  `input_output_aliases`. Untouched rows are untouched BYTES — they
  are never read, let alone written. Row-Adam semantics are torch
  SparseAdam, matching `lazy_embedding.row_adam_update`: moments decay
  only for touched rows, bias correction by the global step count.

Duplicate/empty slots: the compaction puts valid slots first; every
invalid slot redirects its index map to the LAST valid slot's row and
skips its writes (`pl.when`). Consecutive same-index blocks stay
resident in VMEM and flush once, so the skipped writes cannot clobber
the valid update and no slot ever maps to an unwritten block.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.pallas.fused_adam import (_adam_math, _fold_scalars,
                                                 _resolve_interpret)


def segment_compact(ids, d_rows):
    """Sort-dedup-sum the batch's per-example row grads into compacted
    slots. Returns (uids, valid, g_slots):

    - uids[j]  — the j-th unique id for j < n_valid; every later slot
      redirects to the last valid slot's id (the kernel's safe target);
    - valid[j] — 1 for the unique slots, 0 for the redirected tail;
    - g_slots[j] — the segment-summed gradient of uids[j] (0 on the
      tail).

    All static shapes (B slots for a B-row batch), jit/scan friendly.
    """
    B = ids.shape[0]
    ids = ids.astype(jnp.int32)
    order = jnp.argsort(ids)
    sids = ids[order]
    first = jnp.concatenate([jnp.ones((1,), bool), sids[1:] != sids[:-1]])
    seg = jnp.cumsum(first) - 1                    # slot per sorted entry
    n_valid = first.sum()
    g_slots = jnp.zeros_like(d_rows).at[seg].add(d_rows[order])
    uids = jnp.zeros((B,), jnp.int32).at[seg].set(sids)
    slot = jnp.arange(B)
    valid = slot < n_valid
    uids = jnp.where(valid, uids, uids[n_valid - 1])
    return uids, valid.astype(jnp.int32), g_slots


def _row_kernel(b1, b2, uids_ref, valid_ref, s_ref, p_ref, m_ref, v_ref,
                g_ref, p_out, m_out, v_out):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(valid_ref[i] > 0)
    def _():
        g = g_ref[...].astype(jnp.float32)
        p = p_ref[...].astype(jnp.float32)
        p_new, m_new, v_new = _adam_math(p, m_ref[...], v_ref[...], g,
                                         s_ref[0], s_ref[1], s_ref[2],
                                         b1, b2)
        p_out[...] = p_new.astype(p_out.dtype)
        m_out[...] = m_new
        v_out[...] = v_new


def segment_adam_cost(n_slots: int, dim: int,
                      p_dtype=jnp.float32) -> Tuple[int, int]:
    """(flops, bytes): 7 row-passes over the TOUCHED rows only — the
    whole point of the sparse path, and what the cost_estimate tells
    the roofline layer instead of a dense-table sweep."""
    n = n_slots * dim
    pbytes = jnp.dtype(p_dtype).itemsize
    return 12 * n, n * (4 + 2 * pbytes + 4 * 4)


def segment_adam_update(table, mu, nu, ids, d_rows, count, *, lr,
                        b1: float = 0.9, b2: float = 0.999,
                        eps: float = 1e-8,
                        interpret: Optional[bool] = None):
    """Row-sparse Adam over the rows `ids` touches, grads given as
    per-example [B, dim] rows (duplicates summed here). Returns
    (table, mu, nu) with ONLY touched rows rewritten; every other row
    is bitwise the input. `count` is the global step (SparseAdam bias
    correction)."""
    uids, valid, g_slots = segment_compact(ids, d_rows)
    scal = _fold_scalars(count, lr, b1, b2, eps, 0.0)
    return kernel_apply(table, mu, nu, uids, valid, g_slots, scal,
                        b1=b1, b2=b2, interpret=interpret)


def kernel_apply(table, mu, nu, uids, valid, g_slots, scal, *,
                 b1: float = 0.9, b2: float = 0.999,
                 interpret: Optional[bool] = None):
    """The bare fused gather→Adam→scatter kernel over pre-compacted
    slots — split from `segment_adam_update` so the roofline layer can
    lower and cost EXACTLY the pallas region (the compaction's
    sort/scatter upstream is ordinary XLA work that cost analysis
    already counts right)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    interpret = _resolve_interpret(interpret)
    B = uids.shape[0]
    dim = table.shape[1]
    flops, bytes_ = segment_adam_cost(B, dim, table.dtype)
    tab_spec = pl.BlockSpec((1, dim), lambda i, uids, valid: (uids[i], 0))
    slot_spec = pl.BlockSpec((1, dim), lambda i, uids, valid: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  tab_spec, tab_spec, tab_spec, slot_spec],
        out_specs=[tab_spec, tab_spec, tab_spec],
    )
    return pl.pallas_call(
        functools.partial(_row_kernel, b1, b2),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(table.shape, table.dtype),
                   jax.ShapeDtypeStruct(mu.shape, jnp.float32),
                   jax.ShapeDtypeStruct(nu.shape, jnp.float32)],
        # operands: (uids, valid, scal, table, mu, nu, g_slots) — the
        # big tables alias their outputs: in-place row scatter, no
        # full-table copy (the failure mode of the XLA `.at[].set`
        # path)
        input_output_aliases={3: 0, 4: 1, 5: 2},
        cost_estimate=pl.CostEstimate(flops=flops, bytes_accessed=bytes_,
                                      transcendentals=B * dim),
        interpret=interpret,
    )(uids, valid, scal, table, mu, nu, g_slots)


# ---------------------------------------------------------------------------
# fused one-step: rows-reindexed backward + fused dense rest
# ---------------------------------------------------------------------------
def make_fused_one_step(apply_fn, loss_fn, optimizer, specs,
                        apply_and_state_fn=None,
                        mixed_precision: bool = False,
                        interpret: Optional[bool] = None):
    """The fused twin of `lazy_embedding.make_lazy_one_step`: same
    (params, opt_state, xb, yb, rng) signature and the same opt_state
    layout (`lazy_embedding.init_state`), with the declared tables on
    the sparse fused path and every other parameter on `optimizer`
    (the fused dense kernel when the trainer engaged it, plain optax
    otherwise — `fused_apply` duck-typing as in `trainer._make_one_step`).

    Tables whose spec carries `set_ids_fn` take the rows-reindexed
    backward (no dense cotangent); a spec without it falls back to the
    dense gradient with the touched rows gathered after the fact —
    still the fused in-place row update, just not the grad saving."""
    from analytics_zoo_tpu.learn.lazy_embedding import (_get, _key, _set,
                                                        split_rest)
    from analytics_zoo_tpu.learn.trainer import _cast_tree, _merge_state

    reindexed = [s for s in specs if getattr(s, "set_ids_fn", None)]
    dense = [s for s in specs if not getattr(s, "set_ids_fn", None)]
    fused_rest = getattr(optimizer, "fused_apply", None)

    def one_step(params, opt_state, xb, yb, rng):
        ids_by_key = {_key(s): s.ids_fn(xb).astype(jnp.int32)
                      for s in specs}
        # gather the touched rows OUTSIDE the differentiated function
        # and point the model at them through rewritten position ids
        rows_in = {_key(s): _get(params, s.path)[ids_by_key[_key(s)]]
                   for s in reindexed}
        xb_sub = xb
        for s in reindexed:
            pos = jnp.arange(ids_by_key[_key(s)].shape[0], dtype=jnp.int32)
            xb_sub = s.set_ids_fn(xb_sub, pos)
        # differentiate w.r.t. a tree WITHOUT the reindexed table
        # leaves: leaving them in (unused) would make jax materialize a
        # vocab-sized zero cotangent per table — the very pass this
        # path deletes
        params_head = split_rest(params, reindexed)

        def compute_loss(p, rows):
            for s in reindexed:
                p = _set(p, s.path, rows[_key(s)])
            if mixed_precision:
                p = _cast_tree(p, jnp.bfloat16)
                # inputs stay uncast: ids above 256 are not exactly
                # representable in bf16 (see trainer.one_step)
            if apply_and_state_fn is not None:
                pred, state_upd = apply_and_state_fn(p, xb_sub,
                                                     training=True, rng=rng)
            else:
                pred, state_upd = apply_fn(p, xb_sub, training=True,
                                           rng=rng), {}
            if mixed_precision:
                pred = jax.tree_util.tree_map(
                    lambda a: a.astype(jnp.float32), pred)
            return loss_fn(yb, pred), state_upd

        (loss, state_upd), (grads, row_grads) = jax.value_and_grad(
            compute_loss, argnums=(0, 1), has_aux=True)(params_head,
                                                        rows_in)
        if mixed_precision:
            grads = _cast_tree(grads, jnp.float32, only=jnp.bfloat16)
            row_grads = _cast_tree(row_grads, jnp.float32,
                                   only=jnp.bfloat16)
            state_upd = _cast_tree(state_upd, jnp.float32,
                                   only=jnp.bfloat16)

        t = opt_state["t"] + 1
        tables = dict(opt_state["tables"])
        for s in reindexed:
            k = _key(s)
            table, mu, nu = segment_adam_update(
                _get(params, s.path), *tables[k], ids_by_key[k],
                row_grads[k], t, lr=s.lr, b1=s.b1, b2=s.b2, eps=s.eps,
                interpret=interpret)
            params = _set(params, s.path, table)
            tables[k] = (mu, nu)
        for s in dense:
            # dense-cotangent fallback: gather the touched rows of the
            # materialized table grad (duplicates are NOT re-summed —
            # the dense VJP already accumulated them, so feed each
            # unique id its dense-grad row exactly once)
            k = _key(s)
            ids = ids_by_key[k]
            g_table = _get(grads, s.path)
            table, mu, nu = segment_adam_update(
                _get(params, s.path), *tables[k], ids,
                _dedup_rows(g_table, ids), t, lr=s.lr, b1=s.b1, b2=s.b2,
                eps=s.eps, interpret=interpret)
            params = _set(params, s.path, table)
            tables[k] = (mu, nu)

        rest_grads = split_rest(grads, specs)
        rest_params = split_rest(params, specs)
        if fused_rest is not None:
            new_rest, rest_state = fused_rest(rest_grads,
                                              opt_state["rest"],
                                              rest_params)
        else:
            import optax
            updates, rest_state = optimizer.update(
                rest_grads, opt_state["rest"], rest_params)
            new_rest = optax.apply_updates(rest_params, updates)
        params = jax.tree_util.tree_map(
            lambda new, old: old if new is None else new,
            new_rest, params, is_leaf=lambda x: x is None)
        params = _merge_state(params, state_upd)
        return params, {"rest": rest_state, "tables": tables, "t": t}, loss

    return one_step


def _dedup_rows(g_table, ids):
    """Per-example rows of an ALREADY-accumulated dense table grad,
    aligned with the ORIGINAL `ids` order: one entry per unique id
    carries its dense-grad row, every other duplicate carries zeros —
    so `segment_compact`'s re-sum reproduces the dense accumulation
    exactly once per row."""
    ids = ids.astype(jnp.int32)
    order = jnp.argsort(ids)
    sids = ids[order]
    dup_sorted = jnp.concatenate([jnp.zeros((1,), bool),
                                  sids[1:] == sids[:-1]])
    # scatter the sorted-order dup flags back to original positions
    dup = jnp.zeros_like(dup_sorted).at[order].set(dup_sorted)
    return jnp.where(dup[:, None], 0.0, g_table[ids])
