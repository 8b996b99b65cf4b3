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
from analytics_zoo_tpu.pallas import flash_attention as fa
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


def _mosaic_calls_and_vmem(fn, *args):
    """(Mosaic calls in the TPU lowering of `fn`, the scoped-VMEM sizes
    they ask for): `CompilerParams(vmem_limit_bytes=n)` is written into
    the call's configuration as a `scoped_memory_configs` entry of size
    n, and a kernel that leaves it unset has no such entry."""
    import re
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    asked = re.findall(r'scoped_memory_configs[^\]]*?size\\22: (\d+)', text)
    assert len(asked) == text.count("scoped_memory_configs")
    return text.count("tpu_custom_call"), [int(n) for n in asked]


_MIB = 2 ** 20


@pytest.mark.parametrize("T,dtype,n_bwd,asked", [
    (128, jnp.bfloat16, 1, []), (2048, jnp.bfloat16, 1, []),
    (8192, jnp.bfloat16, 1, [20 * _MIB]),
    (8192, jnp.float32, 2, []), (65536, jnp.bfloat16, 2, []),
])
def test_flash_attention_fwd_bwd_dropout(T, dtype, n_bwd, asked):
    q = sds((1, 2, T, 64), dtype)

    def loss(q, k, v):
        out = flash_attention(q, k, v, dropout_rate=0.1,
                              dropout_seed=jnp.int32(3))
        return out.astype(jnp.float32).sum()
    # one backward kernel wherever its VMEM reckoning fits the chip (seq
    # 2048 runs 1024x1024 tiles in 256-column chunks); at 8192 tokens dq
    # for a whole head-batch passes the compiler's default scoped VMEM and
    # the one kernel asks for its need, the only call of the three that
    # asks anything; float32 at 1024 tiles passes the default at one tile
    # and dq at 65,536 tokens passes the ceiling: dq and dk/dv are two
    # kernels there, which ask nothing
    assert _mosaic_calls_and_vmem(jax.grad(loss, argnums=(0, 1, 2)),
                                  q, q, q) == (1 + n_bwd, asked)


@pytest.mark.parametrize("T,D,n_bwd,asked", [
    (4096, 128, 1, []), (4096, 64, 1, []), (8192, 128, 1, [20 * _MIB]),
    (53248, 128, 1, [64 * _MIB]), (54272, 128, 2, []),
])
def test_flash_attention_causal_fwd_bwd(T, D, n_bwd, asked):
    q = sds((2, 16, T, D), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()
    # the causal flag keeps the backward's form: one kernel at the
    # seq-4096 decoder fit's shape (16 heads of 128) under the default,
    # one kernel that asks for 20 MiB at 8192 and for the whole ceiling at
    # the last length under it, the pair past that
    assert _mosaic_calls_and_vmem(jax.grad(loss, argnums=(0, 1, 2)),
                                  q, q, q) == (1 + n_bwd, asked)


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


@pytest.fixture
def default_vmem_only(monkeypatch):
    """A chip whose ceiling is the compiler's default scoped VMEM (the
    TensorCores before the v5e have 16 MiB in all): what passes it gets
    the pair, as every shape did before PR 31."""
    monkeypatch.setattr(fa, "_bwd_fused_vmem_ceiling", lambda: 16 * _MIB)


def _noncausal_digests(B, T):
    q = sds((B, 12, T, 64), jnp.bfloat16)
    mask = sds((B, 1, 1, T), jnp.float32)

    def loss(q, k, v, m):
        out = flash_attention(q, k, v, mask=m, dropout_rate=0.1,
                              dropout_seed=jnp.int32(3))
        return out.astype(jnp.float32).sum()
    return _mosaic_digests(jax.grad(loss, argnums=(0, 1, 2)), q, q, q, mask)


def _causal_digests(T):
    q = sds((2, 16, T, 128), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()
    return _mosaic_digests(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)


def test_the_pair_is_pinned_where_the_default_is_the_ceiling(
        default_vmem_only):
    """The two-kernel backward at 8192 tokens: forward, dq, dk/dv, as PR
    27 (non-causal) and PR 29's tree (causal) pinned them. Since PR 31
    the v5e runs one kernel at this length, so the pair is reached here
    through a ceiling of 16 MiB; its modules have not moved."""
    assert _noncausal_digests(2, 8192) == [
        "536a65b75cd15935", "188e4ba79c910010", "32e288c95dbec228"]
    assert _causal_digests(8192) == [
        "11d9f0777d1fccc1", "24c1ac587205cd70", "33c5655be04178a7"]


@pytest.mark.parametrize("B,T,digests", [
    # bert-base-pos2048.fit-seq2048-flash: forward and the one backward
    (16, 2048, ["1958ff5770fbb10a", "b308d3349e820aa7"]),
    # 8192 tokens: the same forward as the pair's above, and the one
    # backward that asks for 20 MiB (PR 31; made by `_mosaic_digests`)
    (2, 8192, ["536a65b75cd15935", "ce42d9bfe3330642"]),
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
    assert _noncausal_digests(B, T) == digests


@pytest.mark.parametrize("T,digests", [
    # ouro-2.6b.fit-seq4096: forward and the one backward
    (4096, ["7a011828263cef96", "2bdaaa74df8dfb6f"]),
    # 8192 tokens: the pair's forward, and the one backward (PR 31)
    (8192, ["11d9f0777d1fccc1", "f399b5afafb15215"]),
])
def test_causal_kernels_at_one_width_are_pinned_too(T, digests):
    """PR 30 gave every kernel a second width (keys wider than values).
    At one width the causal kernels at heads of 128 lower to the modules
    of PR 29's tree (commit 67b95ef; made by `_mosaic_digests` on it):
    the generalisation moved no instruction of the accepted cells'
    kernels, the non-causal ones above included."""
    assert _causal_digests(T) == digests


@pytest.mark.parametrize("shapes,digests", [
    # lfm2-8b-a1b.fit-seq16384-conv: 32 query heads on 8 K/V heads of 64
    (((1, 32, 16384, 64), (1, 8, 16384, 64), (1, 8, 16384, 64)),
     ["4ad0c75467314bc3", "bbaa1c9d9ed9be6a"]),
    # kanana-2-30b-a3b.fit-seq8192-b2: keys 192 wide, values 128
    (((2, 32, 8192, 192), (2, 32, 8192, 192), (2, 32, 8192, 128)),
     ["1ad078689095a379", "9dde3494dfaf7553"]),
])
def test_grouped_and_two_width_kernels_are_pinned_too(shapes, digests):
    """The sliding window is a static flag: without it (`window=None`) the
    grouped-query and two-width causal kernels of the accepted cells lower
    to the modules they lowered to before the flag existed (made by
    `_mosaic_digests` on the tree of commit f0c2843), as the non-causal
    and one-width causal kernels pinned above do."""
    q, k, v = (sds(s, jnp.bfloat16) for s in shapes)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, window=None).astype(
            jnp.float32).sum()
    assert _mosaic_digests(jax.grad(loss, argnums=(0, 1, 2)),
                           q, k, v) == digests


@pytest.mark.parametrize("T,n_bwd,asked", [
    (1024, 1, None), (8192, 1, 30 * _MIB), (25600, 1, 64 * _MIB),
    (26624, 2, None)])
def test_flash_attention_two_widths_fwd_bwd(T, n_bwd, asked):
    """Keys 192 wide, values 128 (latent attention): one backward kernel
    at one 1024 tile under the compiler's default, one that asks for 30
    MiB at the expert model's 8192 tokens, the pair past the ceiling."""
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
    assert text.count("scoped_memory_configs") == (asked is not None)
    assert (f"size\\22: {asked}}}" in text) == (asked is not None)


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


@pytest.mark.parametrize("held,rows", [((0, 8), 8), ((0, 256), 0)])
def test_expert_layer_moves_rows_by_the_row_kernels_by_name(held, rows):
    """Kimi's expert layer (hidden 2304, 8 of 256 routed experts held, 8 a
    token) at 2048 tokens, forward and backward: gather forward, combine's
    pack and combine forward, combine backward's gather, dispatch backward's
    pack and combine (each gather packs its token rows first), and a whole
    layer keeps XLA's gathers."""
    from analytics_zoo_tpu.keras.moe import MoEFeedForward
    layer = MoEFeedForward(2304, 1024, 256, 8, experts_held=held)
    params = jax.eval_shape(layer.build, jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(lambda a: sds(a.shape, jnp.bfloat16),
                                    params)

    def loss(p, u):
        return layer.routed(p, u).astype(jnp.float32).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(
        params, sds((1, 2048, 2304), jnp.bfloat16)).lower(
            lowering_platforms=("tpu",)).as_text()
    assert layer.row_kernels == bool(rows)
    assert text.count("moe_rows_gather") >= 2 * bool(rows)
    names = ("moe_rows_gather", "moe_rows_gather_pack", "moe_rows_combine",
             "moe_rows_combine_pack")
    calls = sum(text.count(f'name = "{n}"') for n in names)
    assert text.count("tpu_custom_call") == 9 + calls
    assert all((n in text) == bool(rows) for n in names)


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


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_delta_rule_chunk_kernels_lower_under_their_names(dtype):
    """The KDA layers' kernels (`pallas/delta_rule.py`) at the benchmark's
    widths: the chunk's preparation and the pass over the chunks, forward
    and backward, lower for the TPU, in the step's bfloat16 and in the
    forward check's float32, under the names the per-kernel metrics match;
    rows of batch x heads are taken 8 at a time, so 16 rows are one
    `lax.map` of each kernel."""
    from analytics_zoo_tpu.pallas import delta_rule as dr
    N, T, d = 16, 256, 128
    x = sds((N, T, d), dtype)
    args = (x, x, x, sds((N, T, d), jnp.float32), sds((N, T), jnp.float32))

    def loss(*a):
        return dr.gated_delta_rule(*a, chunk=64).astype(jnp.float32).sum()
    fwd = jax.jit(lambda *a: dr.gated_delta_rule(*a, chunk=64)).trace(
        *args).lower(lowering_platforms=("tpu",)).as_text()
    assert fwd.count("tpu_custom_call") == 2
    assert "delta_prepare_fwd" in fwd and "kda_chunk_fwd" in fwd
    bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).trace(
        *args).lower(lowering_platforms=("tpu",)).as_text()
    for name in ("delta_prepare_fwd", "kda_chunk_fwd", "kda_chunk_bwd",
                 "delta_prepare_bwd"):
        assert name in bwd, name
    assert "triangular" not in bwd.lower()
    assert dr._heads_per_step(16) == 8 and dr._heads_per_step(6) == 2


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_kda_layer_lowers_with_the_short_conv_kernels_by_name(dtype):
    """A KDA layer's step at the benchmark's head width (128) and the tile
    its 16,384 tokens are cut into (512; two of them here): the q/k/v
    stage (`pallas/short_conv.py`) lowers for the TPU as
    `qkv_short_conv_fwd`, once each for q, k and v, and its gradient as
    `qkv_short_conv_bwd`, the names `kda_short_conv_time_share` matches,
    beside the recurrence's four kernels."""
    from analytics_zoo_tpu.keras.linear_attention import KimiDeltaAttention
    from analytics_zoo_tpu.pallas import short_conv as sc
    layer = KimiDeltaAttention(256, 4, 128, name="kda_lowered")
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, dtype),
        jax.eval_shape(layer.build, jax.random.PRNGKey(0)))
    x = sds((1, 1024, 256), dtype)
    assert sc.short_conv_fits((1, 1024, 512), 4, 4, None)
    assert sc._tile(16384) == sc._tile(1024) == 512

    def loss(p, x):
        return layer.call(p, x).astype(jnp.float32).sum()
    fwd = jax.jit(layer.call).trace(params, x).lower(
        lowering_platforms=("tpu",)).as_text()
    assert fwd.count("qkv_short_conv_fwd") >= 3
    assert "qkv_short_conv_bwd" not in fwd
    assert fwd.count("tpu_custom_call") == 5     # + the recurrence's two
    bwd = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(params, x).lower(
        lowering_platforms=("tpu",)).as_text()
    for name in ("qkv_short_conv_fwd", "qkv_short_conv_bwd",
                 "delta_prepare_fwd", "delta_prepare_bwd", "kda_chunk_fwd",
                 "kda_chunk_bwd"):
        assert name in bwd, name
    # of the hybrid cell's per-kernel metrics the stage's own reads the two
    # names, and no other does (`kda_time_share` reads every `kda_...`)
    import json
    import os
    import re
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        cell_metrics = [
            m["name"] for m in json.load(fh)["per_layer"]
            if "kimi-linear-48b-a3b.fit-seq16384-b1" in m.get("workloads", [])]
    patterns = {}
    for metric in cell_metrics:
        with open(os.path.join(root, "benchmark", "layer_metrics",
                               metric + ".json")) as fh:
            patterns[metric] = json.load(fh).get("pattern")
    for name in ("qkv_short_conv_fwd", "qkv_short_conv_bwd"):
        assert {m for m, pattern in patterns.items() if pattern and re.search(
            pattern, name + ".7@tpu_custom_call")} \
            == {"kda_short_conv_time_share"}, name
