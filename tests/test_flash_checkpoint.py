"""The flash kernel's residuals across `jax.checkpoint`.

`pallas/flash_attention.py` names the forward kernel's output and
log-sum-exp where it makes them and exports the one policy that keeps
exactly them (`save_flash_residuals`). A checkpointed block that holds a
flash call then computes the rest again in its backward pass, but its
recomputation holds no forward kernel: one `flash_fwd*` and one backward
call an application, where a checkpoint with no policy runs two and one.
Counted in the jaxpr of the gradient (nothing runs), for a bare block and
for the two checkpoints of the package: the looped decoder's and
`BERT(remat=True)`'s. The gradients with the policy are the gradients
without it, bit for bit."""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.keras import transformer
from analytics_zoo_tpu.models import looped_decoder
from analytics_zoo_tpu.pallas.flash_attention import (_reference_attention,
                                                      flash_attention,
                                                      save_flash_residuals)


def _sub_jaxprs(value):
    if isinstance(value, jax.extend.core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jax.extend.core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def _kernel_calls(jaxpr, counts=None):
    """`pallas_call`s of a jaxpr and everything nested in it (scan and
    checkpoint bodies, custom-VJP calls), by kernel family: `fwd` for
    `flash_fwd*`, `bwd` for every backward kernel. A scan's body counts
    once: one layer application."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            assert name.startswith("flash_"), name
            counts["fwd" if name.startswith("flash_fwd") else "bwd"] += 1
            continue        # the kernel's own body is not walked
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                _kernel_calls(sub, counts)
    return counts


def _grad_kernel_calls(loss, *args):
    return dict(_kernel_calls(jax.make_jaxpr(jax.grad(loss))(*args).jaxpr))


def _block(causal):
    """A small attention block of the looped decoder's kind: projection,
    heads, flash call, projection, residual."""
    H, D = 2, 64

    def block(w, x):
        B, T, _ = x.shape
        qkv = (x @ w["qkv"]).reshape(B, T, 3, H, D)
        q, k, v = [jnp.transpose(qkv[:, :, i], (0, 2, 1, 3))
                   for i in range(3)]
        ctx = flash_attention(q, k, v, causal=causal, interpret=True)
        ctx = jnp.transpose(ctx, (0, 2, 1, 3)).reshape(B, T, H * D)
        return x + jnp.tanh(ctx @ w["out"])
    rs = np.random.RandomState(0)
    w = {"qkv": jnp.asarray(rs.randn(H * D, 3 * H * D) * 0.1, jnp.float32),
         "out": jnp.asarray(rs.randn(H * D, H * D) * 0.1, jnp.float32)}
    x = jnp.asarray(rs.randn(2, 256, H * D), jnp.float32)
    return block, w, x


def _loss_of(block, n=2):
    """`n` applications of `block` under a scan, as the models apply it."""
    def loss(w, x):
        h, _ = jax.lax.scan(lambda h, _: (block(w, h), None), x, None,
                            length=n)
        return jnp.sum(h ** 2)
    return loss


@pytest.fixture
def flash_on_cpu(monkeypatch):
    """The models take the kernels over the reference path from
    `jax.default_backend()`; the jaxpr is only traced here."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _looped_decoder_grad_calls(monkeypatch, policy):
    if policy is None:
        monkeypatch.setattr(looped_decoder, "save_flash_residuals", None)
    model = looped_decoder.LoopedDecoderLM(
        vocab=64, hidden_size=256, n_block=2, n_head=2,
        intermediate_size=512, n_pass=2, use_flash=True, remat=True)
    params = jax.eval_shape(lambda: model.build(jax.random.PRNGKey(0)))
    ids = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    return _grad_kernel_calls(
        lambda p, i: jnp.sum(model.hidden_and_gates(p, i)[0] ** 2),
        params, ids), 1        # one block body under the two scans


def _bert_grad_calls(monkeypatch, policy, stacked):
    if policy is None:
        monkeypatch.setattr(
            transformer, "_REMAT_POLICY",
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    n_block = 2
    model = transformer.BERT(
        vocab=64, hidden_size=128, n_block=n_block, n_head=2, seq_len=256,
        intermediate_size=256, use_flash=True, remat=True, stacked=stacked)
    params = jax.eval_shape(
        lambda: model.build(jax.random.PRNGKey(0), None))
    ids = jax.ShapeDtypeStruct((2, 256), jnp.int32)

    def loss(p, i):
        h, _ = model.call(p, [i, jnp.ones_like(i)], training=True,
                          rng=jax.random.PRNGKey(1))
        return jnp.sum(h ** 2)
    # the stacked form scans ONE block body; the other holds every block
    return _grad_kernel_calls(loss, params, ids), 1 if stacked else n_block


def _bare_block_grad_calls(monkeypatch, policy, causal):
    block, w, x = _block(causal)
    return _grad_kernel_calls(
        _loss_of(jax.checkpoint(block, policy=policy)), w, x), 1


_CHECKPOINTS = {
    "block": functools.partial(_bare_block_grad_calls, causal=False),
    "block_causal": functools.partial(_bare_block_grad_calls, causal=True),
    "looped_decoder": _looped_decoder_grad_calls,
    "bert_remat_stacked": functools.partial(_bert_grad_calls, stacked=True),
    "bert_remat": functools.partial(_bert_grad_calls, stacked=False),
}


@pytest.mark.parametrize("kept", [True, False],
                         ids=["policy", "no_policy"])
@pytest.mark.parametrize("where", sorted(_CHECKPOINTS))
def test_recomputation_holds_no_forward_kernel(where, kept, monkeypatch,
                                               flash_on_cpu):
    calls, bodies = _CHECKPOINTS[where](
        monkeypatch, save_flash_residuals if kept else None)
    # with the policy: the forward pass's call and the backward kernel;
    # without: the recomputation runs the forward kernel a second time
    assert calls == {"fwd": (1 if kept else 2) * bodies, "bwd": bodies}


@pytest.mark.parametrize("causal", [False, True])
def test_without_a_checkpoint_the_names_change_nothing(causal):
    block, w, x = _block(causal)
    assert _grad_kernel_calls(_loss_of(block), w, x) == {"fwd": 1, "bwd": 1}
    text = jax.jit(jax.grad(_loss_of(block))).lower(w, x).as_text()
    assert "attention_kernel" not in text      # a name lowers to nothing


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_with_the_policy_are_the_gradients_without_it(causal):
    block, w, x = _block(causal)
    grads = {}
    for key, fn in (("policy", jax.checkpoint(block,
                                              policy=save_flash_residuals)),
                    ("no_policy", jax.checkpoint(block)),
                    ("no_checkpoint", block)):
        grads[key] = jax.jit(jax.grad(_loss_of(fn), argnums=(0, 1)))(w, x)
    for other in ("no_policy", "no_checkpoint"):
        for a, b in zip(jax.tree_util.tree_leaves(grads["policy"]),
                        jax.tree_util.tree_leaves(grads[other])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # and the reference's, to tests/test_flash_vjp.py's tolerances (the
    # absolute one relative to the leaf's largest element: a weight's
    # gradient is a sum over 512 rows and two applications)
    def ref_block(w, x):
        B, T, _ = x.shape
        qkv = (x @ w["qkv"]).reshape(B, T, 3, 2, 64)
        q, k, v = [jnp.transpose(qkv[:, :, i], (0, 2, 1, 3))
                   for i in range(3)]
        ctx = _reference_attention(q, k, v, causal=causal)
        ctx = jnp.transpose(ctx, (0, 2, 1, 3)).reshape(B, T, 128)
        return x + jnp.tanh(ctx @ w["out"])
    ref = jax.grad(_loss_of(ref_block), argnums=(0, 1))(w, x)
    for a, b in zip(jax.tree_util.tree_leaves(grads["policy"]),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("remat,use_flash,expected", [
    (True, True, 0), (True, False, 1), (False, True, 0), (False, False, 0)])
def test_gauge_says_whether_the_attention_forward_runs_again(remat,
                                                             use_flash,
                                                             expected):
    from analytics_zoo_tpu.observability.registry import get_registry
    model = looped_decoder.LoopedDecoderLM(
        vocab=8, hidden_size=16, n_block=1, n_head=2, intermediate_size=16,
        use_flash=use_flash, remat=remat,
        name=f"gauge_case_{int(remat)}{int(use_flash)}")
    gauge = get_registry().get("model_recompute_attention_kernel")
    assert gauge.value(model=model.name) == expected
