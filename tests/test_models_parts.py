"""Model-component correctness: SSD vs recurrence, decode-vs-forward
consistency, chunked attention vs naive, chunked xent vs naive, MoE/rope."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import BlockSpec, ModelConfig
from repro.models import build_model
from repro.models.attention import _flash, _sliding
from repro.models.common import chunked_xent
from repro.models.mamba2 import _ssd_chunked, ssd_reference
from repro.models.transformer import forward_hidden, _unembed

KEY = jax.random.PRNGKey(0)


def test_ssd_chunked_matches_recurrence():
    B, L, H, P, N = 2, 47, 3, 8, 16
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, L, H, P))
    b = jax.random.normal(ks[1], (B, L, N))
    c = jax.random.normal(ks[2], (B, L, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, L, H)))
    a = -jnp.exp(jax.random.normal(ks[4], (H,)))
    yr, hr = ssd_reference(x, b, c, dt, a)
    for chunk in (8, 16, 64):
        y, h = _ssd_chunked(x, b, c, dt, a, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=1e-3, atol=1e-4)


def test_ssd_chunked_gradient_finite_across_long_decay():
    """A chunk spanning more decay than f32 exp can hold (here 64 tokens x
    2 nats) keeps finite gradients; at mamba2-130m's published chunk of
    256 tokens the step-0 gradient was NaN."""
    B, L, H, P, N = 1, 64, 2, 4, 8
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (B, L, H, P))
    b = jax.random.normal(ks[1], (B, L, N))
    c = jax.random.normal(ks[2], (B, L, N))
    dt = jnp.full((B, L, H), 2.0)
    a = -jnp.ones((H,))

    def loss(x, dt, a):
        y, h = _ssd_chunked(x, b, c, dt, a, chunk=64)
        return jnp.sum(y ** 2) + jnp.sum(h ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(x, dt, a)
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g)))
    yr, _ = ssd_reference(x, b, c, dt, a)
    y, _ = _ssd_chunked(x, b, c, dt, a, chunk=64)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-3,
                               atol=1e-4)


def _naive_attn(q, k, v, causal=True, window=None):
    b, s, h, d = q.shape
    kv = k.shape[2]
    rep = h // kv
    qg = q.reshape(b, s, kv, rep, d)
    sc = jnp.einsum("bqgrd,bkgd->bqgrk", qg, k) / jnp.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool)) if causal else jnp.ones((s, s), bool)
    if window is not None:
        idx = jnp.arange(s)
        mask &= (idx[None, :] > idx[:, None] - window)
    sc = jnp.where(mask[None, :, None, None, :], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bqgrk,bkgd->bqgrd", p, v).reshape(b, s, h, d)


@pytest.mark.parametrize("s,qc,kc", [(37, 8, 8), (64, 16, 32), (16, 64, 64)])
def test_flash_matches_naive(s, qc, kc):
    b, h, kv, d = 2, 4, 2, 8
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    got = _flash(q, k, v, causal=True, prefix_len=0, q_chunk=qc, kv_chunk=kc)
    want = _naive_attn(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_flash_prefix_lm():
    b, s, h, d, pfx = 1, 24, 2, 8, 7
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    got = _flash(q, k, v, causal=True, prefix_len=pfx, q_chunk=8, kv_chunk=8)
    # naive with prefix: position j visible to i if j<=i or j<prefix
    sc = jnp.einsum("bqhd,bkhd->bqhk", q, k) / jnp.sqrt(d)
    idx = jnp.arange(s)
    mask = (idx[None, :] <= idx[:, None]) | (idx[None, :] < pfx)
    sc = jnp.where(mask[None, :, None, :], sc, -1e30)
    want = jnp.einsum("bqhk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("window", [4, 16])
def test_sliding_matches_naive(window):
    b, s, h, kv, d = 2, 40, 4, 2, 8
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    got = _sliding(q, k, v, window=window, q_chunk=8)
    want = _naive_attn(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_chunked_xent_matches_naive():
    b, s, d, v = 2, 19, 8, 37
    ks = jax.random.split(KEY, 3)
    h = jax.random.normal(ks[0], (b, s, d))
    emb = jax.random.normal(ks[1], (v, d))
    labels = jax.random.randint(ks[2], (b, s), 0, v)
    got = chunked_xent(h, emb, labels, chunk=4)
    logits = h @ emb.T
    lse = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    want = jnp.mean(lse - tgt)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


@pytest.mark.parametrize("arch", ["qwen2-7b", "mamba2-130m", "jamba-v0.1-52b",
                                  "mixtral-8x22b", "paligemma-3b"])
def test_decode_matches_forward(arch):
    """Prefill t tokens then decode token t; logits must match the full
    forward at position t (cache correctness across attn/ssm/moe/vlm).

    MoE archs use a high capacity factor here: at the training default the
    *forward* may legitimately drop assignments under capacity pressure,
    while the decode path is no-drop by design — the equality being tested
    is cache correctness, not drop behaviour."""
    cfg = dataclasses.replace(get_config(arch).reduced(), capacity_factor=8.0)
    model = build_model(cfg, remat=False, q_chunk=8, kv_chunk=8)
    params = model.init(KEY)
    b, t = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(5), (b, t + 1), 0, cfg.vocab_size)
    pb = {"tokens": toks[:, :t]}
    prefix = 0
    if cfg.family == "vlm":
        pb["image_emb"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(6), (b, cfg.num_prefix_tokens, cfg.d_model))
        prefix = cfg.num_prefix_tokens
    logits_p, cache = model.prefill(params, pb)
    # grow self-attn cache capacity by 1 slot for the decode write
    def grow(c):
        out = {}
        for pk, sub in c.items():
            out[pk] = {k: (jnp.pad(v, ((0, 0), (0, 0), (0, 1), (0, 0), (0, 0)))
                           if k in ("k", "v") else v)
                       for k, v in sub.items()}
        return out
    cache = grow(cache)
    logits_d, _ = model.decode_step(params, cache, toks[:, t:t + 1],
                                    jnp.asarray(t + prefix, jnp.int32))
    # full forward over t+1 tokens
    fb = {"tokens": toks}
    kw = {}
    if cfg.family == "vlm":
        kw["prefix_emb"] = pb["image_emb"]
    h, _ = forward_hidden(params, cfg, toks, remat=False, q_chunk=8, kv_chunk=8, **kw)
    logits_f = h[:, -1].astype(jnp.float32) @ _unembed(params, cfg).astype(jnp.float32).T
    np.testing.assert_allclose(np.asarray(logits_d), np.asarray(logits_f),
                               rtol=2e-3, atol=2e-3)


def test_moe_routes_all_tokens_with_capacity_slack():
    from repro.models.moe import moe, moe_params
    from repro.models.common import init_maker
    d, e, k, ff = 16, 4, 2, 32
    params = moe_params(init_maker(KEY), "m", d_model=d, moe_d_ff=ff,
                        num_experts=e, num_shared_experts=0, activation="swiglu")
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, d))
    y, aux = moe(params, x, num_experts=e, top_k=k, activation="swiglu",
                 capacity_factor=4.0, group_size=48)
    assert y.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(y)))
    assert float(aux) >= 1.0 - 1e-3  # switch aux loss lower bound at uniformity
