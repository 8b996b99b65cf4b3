"""Every Pallas kernel cross-lowered for the TPU platform, from the CPU.

`jit(...).trace(...).lower(lowering_platforms=("tpu",))` runs jax's Mosaic
lowering without a chip: `pl.CostEstimate` validation, the block-shape rule
(last two block dims multiples of (8, 128) or the whole array extent) and
unsupported primitives all raise here, in seconds. It is the cheap half of
the on-chip suite (`tests/tpu`, which also runs libtpu's compiler and checks
numerics): the CPU tests run the kernels through the interpreter only, which
is how six CostEstimate sites and both decode kernels stopped lowering while
tier-1 stayed green.
"""

import jax
import jax.numpy as jnp
import pytest

from analytics_zoo_tpu.pallas import dropout as dropout_mod
from analytics_zoo_tpu.pallas.decode_attention import (decode_attention,
                                                       paged_decode_attention)
from analytics_zoo_tpu.pallas.flash_attention import flash_attention
from analytics_zoo_tpu.pallas.fused_adam import fused_adam_step
from analytics_zoo_tpu.pallas.segment_update import segment_adam_update

sds = jax.ShapeDtypeStruct


@pytest.fixture(autouse=True)
def tpu_backend(monkeypatch):
    """The kernels pick Mosaic over their reference / the interpreter from
    `jax.default_backend()`; nothing here executes."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _mosaic_calls(fn, *args) -> int:
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    return text.count("tpu_custom_call")


@pytest.mark.parametrize("T,n_bwd", [(128, 1), (2048, 1), (8192, 2)])
def test_flash_attention_fwd_bwd_dropout(T, n_bwd):
    q = sds((1, 2, T, 64), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention(q, k, v, dropout_rate=0.1,
                              dropout_seed=jnp.int32(3))
        return out.astype(jnp.float32).sum()
    # one backward kernel wherever its VMEM reckoning fits (seq 2048 runs
    # 1024x1024 tiles in 256-column chunks); at 8192 tokens dq for a whole
    # head-batch no longer stays on the chip, so dq and dk/dv are two kernels
    assert _mosaic_calls(jax.grad(loss, argnums=(0, 1, 2)), q, q, q) \
        == 1 + n_bwd


@pytest.mark.parametrize("T,D,n_bwd", [(4096, 128, 1), (4096, 64, 1),
                                       (8192, 128, 2)])
def test_flash_attention_causal_fwd_bwd(T, D, n_bwd):
    q = sds((2, 16, T, D), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()
    # the causal flag keeps the backward's form: one kernel at the
    # seq-4096 decoder fit's shape (16 heads of 128), the pair at 8192
    assert _mosaic_calls(jax.grad(loss, argnums=(0, 1, 2)), q, q, q) \
        == 1 + n_bwd


def _mosaic_digests(fn, *args):
    """A digest of every Mosaic module in the TPU lowering of `fn`, printed
    WITHOUT source locations: the module that `tpu_custom_call` carries is
    MLIR bytecode with the kernel's Python line numbers in it, so the
    lowered text itself moves with every edit of the file."""
    import base64
    import hashlib
    import re

    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    out = []
    for m in re.finditer(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text):
        ctx = jmlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(base64.b64decode(m.group(1))) \
                .operation.get_asm(enable_debug_info=False)
        out.append(hashlib.sha256(asm.encode()).hexdigest()[:16])
    return out


@pytest.mark.parametrize("B,T,digests", [
    # bert-base-pos2048.fit-seq2048-flash: forward and the one backward
    (16, 2048, ["1958ff5770fbb10a", "b308d3349e820aa7"]),
    # the two-kernel backward: forward, dq, dk/dv
    (2, 8192, ["536a65b75cd15935", "188e4ba79c910010", "32e288c95dbec228"]),
])
def test_noncausal_kernels_are_pinned_instruction_for_instruction(B, T,
                                                                  digests):
    """The causal flag is static: without it the kernels lower to the
    modules pinned here, so a change meant for the causal form, or for a
    caller, cannot move the flash cell's kernels unseen. The forward's
    digests are those of PR 29's tree (the child of commit 0a7f064; made
    by this function on it), where the forward walks its tile in two
    column chunks with the scale on the q block, the dropout a select and
    its gain at the flush: they moved on purpose, from 27795ef4856c42d2
    and b17463ae723999e6. The three backward kernels' are PR 27's still
    (commit ba9a553: the log-sum-exp a `[B*H, 1, T]` row, a row statistic
    a `[block_q, 128]` array): PR 29 left them instruction for
    instruction. PR 25's (commit 07fb4ab, `[B*H, T, 1]`) were
    6d1e6e9ef29c8231, 1db37abbc2ba0f14 and 630378003e1117a3,
    374bc763c8822ec1, 223abe34395f4d43. A change to the non-causal
    kernels has to change them here, knowingly."""
    q = sds((B, 12, T, 64), jnp.bfloat16)
    mask = sds((B, 1, 1, T), jnp.float32)

    def loss(q, k, v, m):
        out = flash_attention(q, k, v, mask=m, dropout_rate=0.1,
                              dropout_seed=jnp.int32(3))
        return out.astype(jnp.float32).sum()
    assert _mosaic_digests(jax.grad(loss, argnums=(0, 1, 2)),
                           q, q, q, mask) == digests


@pytest.mark.parametrize("T,digests", [
    # ouro-2.6b.fit-seq4096: forward and the one backward
    (4096, ["7a011828263cef96", "2bdaaa74df8dfb6f"]),
    # the two-kernel backward: forward, dq, dk/dv
    (8192, ["11d9f0777d1fccc1", "24c1ac587205cd70", "33c5655be04178a7"]),
])
def test_causal_kernels_at_one_width_are_pinned_too(T, digests):
    """PR 30 gave every kernel a second width (keys wider than values).
    At one width the causal kernels at heads of 128 lower to the modules
    of PR 29's tree (commit 67b95ef; made by `_mosaic_digests` on it):
    the generalisation moved no instruction of the accepted cells'
    kernels, the non-causal ones above included."""
    q = sds((2, 16, T, 128), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()
    assert _mosaic_digests(jax.grad(loss, argnums=(0, 1, 2)), q, q, q) \
        == digests


@pytest.mark.parametrize("T,n_bwd", [(1024, 1), (8192, 2)])
def test_flash_attention_two_widths_fwd_bwd(T, n_bwd):
    """Keys 192 wide, values 128 (latent attention): one backward kernel
    at one 1024 tile, the pair at the expert model's 8192 tokens."""
    q = sds((2, 32, T, 192), jnp.bfloat16)
    v = sds((2, 32, T, 128), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, q, v).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1 + n_bwd
    assert "flash_fwd_causal_mla" in text
    assert ("flash_bwd_fused_causal_mla" in text) == (n_bwd == 1)
    assert ("flash_dq_causal_mla" in text) == (n_bwd == 2)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("k,n", [(2048, 768), (768, 2048)])
def test_grouped_matmul_fwd_and_both_gradients(k, n, dtype):
    """The expert layer's three products at the published widths, 16 held
    experts, the 2 x 8192 x 6-row buffer: forward, d lhs and d rhs are one
    Mosaic call each, named for the trace."""
    from analytics_zoo_tpu.pallas.grouped_matmul import grouped_matmul

    def loss(lhs, rhs, sizes):
        return grouped_matmul(lhs, rhs, sizes).astype(jnp.float32).sum()
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).trace(
        sds((98304, k), dtype), sds((16, k, n), dtype),
        sds((16,), jnp.int32)).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 3
    for name in ("moe_gmm_fwd", "moe_gmm_dlhs", "moe_gmm_drhs"):
        assert name in text


@pytest.mark.parametrize("H,D,L", [(4, 64, 256), (2, 8, 128)])
def test_decode_attention(H, D, L):
    S = 8
    pool = sds((S, H, L, D), jnp.float32)
    assert _mosaic_calls(
        lambda q, k, v, n: decode_attention(q, k, v, n, kv_bucket=L // 2),
        sds((S, H, D), jnp.float32), pool, pool, sds((S,), jnp.int32)) == 1


@pytest.mark.parametrize("H,D,block_len", [(4, 64, 64), (2, 8, 16)])
def test_paged_decode_attention(H, D, block_len):
    S, n_kb = 8, 4
    pool = sds((S * n_kb + 1, H, block_len, D), jnp.float32)
    assert _mosaic_calls(
        lambda q, k, v, t, n: paged_decode_attention(
            q, k, v, t, n, kv_bucket=n_kb * block_len),
        sds((S, H, D), jnp.float32), pool, pool, sds((S, n_kb), jnp.int32),
        sds((S,), jnp.int32)) == 1


def test_fused_adam_bert_and_ncf_leaves():
    shapes = [(768, 3072), (768,), (30522, 768), (768, 2), (3, 5, 11),
              (138000, 64), (32,)]
    p = {f"p{i}": sds(s, jnp.float32) for i, s in enumerate(shapes)}
    assert _mosaic_calls(
        lambda p, m, v, g: fused_adam_step(p, m, v, g, 1, lr=1e-3),
        p, p, p, p) == len(shapes)


def test_pallas_dropout(monkeypatch):
    monkeypatch.setenv("ZOO_DROPOUT_IMPL", "pallas")

    def loss(x):
        return dropout_mod.fused_dropout(x, 0.1, seed=jnp.int32(1)).sum()
    assert _mosaic_calls(jax.grad(loss), sds((256, 384), jnp.float32)) == 1
    # a named implementation runs or raises: (5, 7) has no lane-aligned view
    with pytest.raises(ValueError, match="multiple of 128"):
        _mosaic_calls(loss, sds((5, 7), jnp.float32))


def test_segment_adam_update_does_not_lower():
    """ROADMAP D12, pinned so a repair has to come through here: the kernel
    addresses ONE table row per grid step with a (1, dim) block."""
    V, dim, B = 138000, 64, 8192
    t = sds((V, dim), jnp.float32)
    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        _mosaic_calls(
            lambda t, m, v, i, r: segment_adam_update(t, m, v, i, r, 1,
                                                      lr=1e-3),
            t, t, t, sds((B,), jnp.int32), sds((B, dim), jnp.float32))
