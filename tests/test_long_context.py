"""Sequence-sharded (long_500k-style) decode attention correctness:
the LSE-combined shard_map path must match the plain cached attention.
Subprocess inline programs go through repro.compat (see mesh_harness)."""
from mesh_harness import run_py


def test_sharded_decode_attention_matches_dense():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro import compat
        from repro.models.attention import decode_attention, attn_params
        from repro.models.common import init_maker

        mesh = compat.make_mesh((4, 2), ("data", "model"))
        B, S, H, KV, hd, D = 1, 64, 4, 2, 16, 32
        params = attn_params(init_maker(jax.random.PRNGKey(0)), "a",
                             d_model=D, num_heads=H, num_kv_heads=KV,
                             head_dim=hd, qkv_bias=False)
        x = jax.random.normal(jax.random.PRNGKey(1), (B, 1, D))
        cache = {
            "k": jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, hd)),
            "v": jax.random.normal(jax.random.PRNGKey(3), (B, S, KV, hd)),
        }
        pos = jnp.asarray(S - 1, jnp.int32)
        kw = dict(num_heads=H, num_kv_heads=KV, head_dim=hd, rope_theta=1e4)

        # dense reference
        out_ref, cache_ref = decode_attention(params, x, cache, pos, **kw)

        # sequence-sharded path under jit with the cache sharded over 'data'
        kv_sh = NamedSharding(mesh, P(None, "data", None, None))
        cache_sh = jax.tree_util.tree_map(lambda c: jax.device_put(c, kv_sh), cache)
        with compat.use_mesh(mesh):
            out_s, cache_s = jax.jit(
                lambda p, xx, cc, pp: decode_attention(
                    p, xx, cc, pp, seq_shard_axis="data", **kw)
            )(params, x, cache_sh, pos)
        np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_ref),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(cache_s["k"]), np.asarray(cache_ref["k"]),
                                   rtol=1e-5, atol=1e-5)
        # windowed variant
        out_w, _ = decode_attention(params, x, cache, pos, window=16, **kw)
        with compat.use_mesh(mesh):
            out_ws, _ = jax.jit(
                lambda p, xx, cc, pp: decode_attention(
                    p, xx, cc, pp, seq_shard_axis="data", window=16, **kw)
            )(params, x, cache_sh, pos)
        np.testing.assert_allclose(np.asarray(out_ws), np.asarray(out_w),
                                   rtol=2e-4, atol=2e-4)
        print("SHARDED_DECODE_OK")
    """, timeout=120)
    assert "SHARDED_DECODE_OK" in out


def test_whisper_decode_matches_forward():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import build_model
        from repro.models import encdec, transformer as tfm

        cfg = get_config("whisper-tiny").reduced()
        model = build_model(cfg, remat=False, q_chunk=8, kv_chunk=8)
        params = model.init(jax.random.PRNGKey(0))
        b, t = 2, 10
        toks = jax.random.randint(jax.random.PRNGKey(5), (b, t + 1), 0, cfg.vocab_size)
        audio = 0.05 * jax.random.normal(jax.random.PRNGKey(6),
                                         (b, cfg.encoder_seq, cfg.d_model))
        logits_p, cache = model.prefill(params, {"tokens": toks[:, :t], "audio_emb": audio})
        cache = {pk: {k: (jnp.pad(v, ((0,0),(0,0),(0,1),(0,0),(0,0)))
                          if k in ("k", "v") else v) for k, v in sub.items()}
                 for pk, sub in cache.items()}
        logits_d, _ = model.decode_step(params, cache, toks[:, t:t+1],
                                        jnp.asarray(t, jnp.int32))
        enc = encdec.encode(params, cfg, audio, remat=False, q_chunk=8, kv_chunk=8)
        dcfg = encdec._decoder_cfg(cfg)
        h, _ = tfm.forward_hidden(params["decoder"], dcfg, toks, enc_out=enc,
                                  remat=False, q_chunk=8, kv_chunk=8)
        lf = h[:, -1].astype(jnp.float32) @ tfm._unembed(params["decoder"], dcfg).astype(jnp.float32).T
        np.testing.assert_allclose(np.asarray(logits_d), np.asarray(lf),
                                   rtol=2e-3, atol=2e-3)
        print("WHISPER_OK")
    """, devices=1, timeout=120)
    assert "WHISPER_OK" in out
