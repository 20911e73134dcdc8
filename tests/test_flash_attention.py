"""Flash attention kernel tests (interpret mode on CPU; real-TPU execution
is covered by bench/ops microbenches)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.ops.flash_attention import (_blockwise_reference,
                                         flash_attention, mha)
from edl_tpu.parallel.ring_attention import dense_attention


def _qkv(b=2, h=2, s=64, d=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, h, s, d).astype(np.float32)) * 0.4
    return mk(), mk(), mk()


def _dense_bhsd(q, k, v, causal):
    # dense_attention uses [b, s, h, d]
    out = dense_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal=causal)
    return out.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s", [64, 96])  # 96 → ragged last kv block
def test_flash_matches_dense(causal, s):
    q, k, v = _qkv(s=s)
    want = _dense_bhsd(q, k, v, causal)
    got = flash_attention(q, k, v, causal, None, 32, 32, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_blockwise_reference_matches_dense():
    q, k, v = _qkv(s=80)
    for causal in (False, True):
        want = _dense_bhsd(q, k, v, causal)
        got = _blockwise_reference(q, k, v, causal, q.shape[-1] ** -0.5,
                                   block_k=32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_gradients_match_dense(causal):
    q, k, v = _qkv(s=48, d=8)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal, None, 16, 16, True)
                ** 2).sum()

    def loss_dense(q, k, v):
        return (_dense_bhsd(q, k, v, causal) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_bert_flash_matches_dense():
    from edl_tpu.models import bert
    kw = dict(num_layers=2, d_model=32, num_heads=2, mlp_dim=64,
              vocab_size=100, max_len=64, dtype=jnp.float32)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 100, (2, 32)),
                      jnp.int32)
    m_dense = bert.Bert(**kw)
    m_flash = bert.Bert(use_flash=True, **kw)
    params = m_dense.init(jax.random.PRNGKey(0), ids)["params"]
    out_d = m_dense.apply({"params": params}, ids)
    out_f = m_flash.apply({"params": params}, ids)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               rtol=2e-4, atol=2e-4)


def test_mha_layout_wrapper():
    q, k, v = _qkv(s=32)
    qs, ks, vs = (x.transpose(0, 2, 1, 3) for x in (q, k, v))  # [b,s,h,d]
    got = mha(qs, ks, vs, causal=False, interpret=True)
    want = _dense_bhsd(q, k, v, False).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
