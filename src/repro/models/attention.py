"""Grouped-query attention: training (flash-style chunked), sliding-window,
cross-attention, KV-cache decode, and sequence-sharded long-context decode.

Everything is pure JAX (``lax.scan`` online-softmax); the (S, S) score matrix
is never materialized, so 32k-token training/prefill fits activation memory.
The long-context decode path (``sharded_decode_attn``) LSE-combines partial
attention across a mesh axis that shards the KV cache sequence dim -- the
TPU-native answer to 500k-token decode (DESIGN.md Sec. 2).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.models.common import apply_rope

NEG_INF = -1e30


def attn_params(make, prefix: str, *, d_model: int, num_heads: int,
                num_kv_heads: int, head_dim: int, qkv_bias: bool,
                cross: bool = False):
    """Parameter subtree for one attention block (weights stored flattened
    as (D, H*hd) so tensor-parallel sharding works even when H itself does
    not divide the model axis)."""
    p = {
        "wq": make(f"{prefix}.wq", (d_model, num_heads * head_dim), P(None, "model")),
        "wk": make(f"{prefix}.wk", (d_model, num_kv_heads * head_dim), P(None, "model")),
        "wv": make(f"{prefix}.wv", (d_model, num_kv_heads * head_dim), P(None, "model")),
        "wo": make(f"{prefix}.wo", (num_heads * head_dim, d_model), P("model", None)),
    }
    if qkv_bias:
        p["bq"] = make(f"{prefix}.bq", (num_heads * head_dim,), P("model"), "zeros")
        p["bk"] = make(f"{prefix}.bk", (num_kv_heads * head_dim,), P("model"), "zeros")
        p["bv"] = make(f"{prefix}.bv", (num_kv_heads * head_dim,), P("model"), "zeros")
    return p


def _project_qkv(params, x, kv_x, num_heads, num_kv_heads, head_dim):
    b, s, _ = x.shape
    sk = kv_x.shape[1]
    q = x @ params["wq"]
    k = kv_x @ params["wk"]
    v = kv_x @ params["wv"]
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(b, s, num_heads, head_dim)
    k = k.reshape(b, sk, num_kv_heads, head_dim)
    v = v.reshape(b, sk, num_kv_heads, head_dim)
    return q, k, v


def _flash(q, k, v, *, causal: bool, prefix_len: int, q_chunk: int, kv_chunk: int,
           q_offset: int = 0):
    """Online-softmax attention.  q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd).

    ``causal``: causal mask with an optional bidirectional prefix of length
    ``prefix_len`` (PaliGemma-style prefix-LM).  ``q_offset``: absolute
    position of q[0] (for windows/caches).  GQA handled by head repetition
    in-register (no memory blowup: repeat happens on the (chunk, chunk)
    score tile).
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    kv = k.shape[2]
    rep = h // kv
    scale = hd ** -0.5
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, sk)
    nq = -(-sq // q_chunk)
    nk = -(-sk // kv_chunk)
    pad_q = nq * q_chunk - sq
    pad_k = nk * kv_chunk - sk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    # (nq, B, qc, H, hd) etc.
    qs = q.reshape(b, nq, q_chunk, h, hd).transpose(1, 0, 2, 3, 4)
    ks = k.reshape(b, nk, kv_chunk, kv, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, nk, kv_chunk, kv, hd).transpose(1, 0, 2, 3, 4)

    q_pos_base = jnp.arange(q_chunk)
    k_pos_base = jnp.arange(kv_chunk)

    def q_body(_, qc_i):
        qc, qi = qc_i
        qpos = q_offset + qi * q_chunk + q_pos_base  # (qc,)

        def kv_body(carry, kc_i):
            m, l, o = carry
            kc, vc, ki = kc_i
            kpos = ki * kv_chunk + k_pos_base  # (kc,)
            # scores: (B, qc, KV, rep, kc)
            qg = qc.reshape(b, q_chunk, kv, rep, hd)
            s_ = jnp.einsum("bqgrd,bkgd->bqgrk", qg.astype(jnp.float32),
                            kc.astype(jnp.float32)) * scale
            if causal:
                allowed = (kpos[None, :] <= qpos[:, None]) | (kpos[None, :] < prefix_len)
                s_ = jnp.where(allowed[None, :, None, None, :], s_, NEG_INF)
            if pad_k:
                valid_k = kpos < sk
                s_ = jnp.where(valid_k[None, None, None, None, :], s_, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s_, axis=-1))
            p = jnp.exp(s_ - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            o_new = o * corr[..., None] + jnp.einsum(
                "bqgrk,bkgd->bqgrd", p, vc.astype(jnp.float32))
            return (m_new, l_new, o_new), None

        m0 = jnp.full((b, q_chunk, kv, rep), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, q_chunk, kv, rep), jnp.float32)
        o0 = jnp.zeros((b, q_chunk, kv, rep, hd), jnp.float32)
        (m, l, o), _ = jax.lax.scan(kv_body, (m0, l0, o0),
                                    (ks, vs, jnp.arange(nk)))
        out = o / jnp.maximum(l[..., None], 1e-30)
        return None, out.reshape(b, q_chunk, h, hd)

    _, outs = jax.lax.scan(q_body, None, (qs, jnp.arange(nq)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nq * q_chunk, h, hd)
    return out[:, :sq].astype(q.dtype)


def _sliding(q, k, v, *, window: int, q_chunk: int):
    """Sliding-window causal attention with true sub-quadratic compute: each
    query chunk attends a dynamic slice of K/V of static length
    window + q_chunk."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    q_chunk = min(q_chunk, sq)
    nq = -(-sq // q_chunk)
    pad_q = nq * q_chunk - sq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    span = window + q_chunk
    # Left-pad K/V by `window` so every chunk's slice is in range.
    kp = jnp.pad(k, ((0, 0), (window, pad_q), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, pad_q), (0, 0), (0, 0)))
    qs = q.reshape(b, nq, q_chunk, h, hd).transpose(1, 0, 2, 3, 4)

    def q_body(_, qc_i):
        qc, qi = qc_i
        start = qi * q_chunk  # in padded-K coords this is where the span starts
        kc = jax.lax.dynamic_slice_in_dim(kp, start, span, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(vp, start, span, axis=1)
        qpos = start + jnp.arange(q_chunk)  # absolute position (unpadded coords)
        kpos = start - window + jnp.arange(span)
        rep = h // kv
        qg = qc.reshape(b, q_chunk, kv, rep, hd)
        s_ = jnp.einsum("bqgrd,bkgd->bqgrk", qg.astype(jnp.float32),
                        kc.astype(jnp.float32)) * (hd ** -0.5)
        allowed = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] > qpos[:, None] - window) & (kpos[None, :] >= 0)
        s_ = jnp.where(allowed[None, :, None, None, :], s_, NEG_INF)
        p = jax.nn.softmax(s_, axis=-1)
        out = jnp.einsum("bqgrk,bkgd->bqgrd", p, vc.astype(jnp.float32))
        return None, out.reshape(b, q_chunk, h, hd)

    _, outs = jax.lax.scan(q_body, None, (qs, jnp.arange(nq)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nq * q_chunk, h, hd)
    return out[:, :sq].astype(q.dtype)


def attention(params, x, *, num_heads: int, num_kv_heads: int, head_dim: int,
              rope_theta: Optional[float] = 1e4, causal: bool = True,
              window: Optional[int] = None, prefix_len: int = 0,
              cross_kv: Optional[jnp.ndarray] = None,
              positions: Optional[jnp.ndarray] = None,
              q_chunk: int = 1024, kv_chunk: int = 1024,
              return_kv: bool = False):
    """Full attention sublayer for training/prefill.  x: (B, S, D).

    ``return_kv``: also return the (roped) K/V so prefill can populate the
    decode cache."""
    b, s, _ = x.shape
    kv_x = cross_kv if cross_kv is not None else x
    q, k, v = _project_qkv(params, x, kv_x, num_heads, num_kv_heads, head_dim)
    if rope_theta is not None and cross_kv is None:
        pos = positions if positions is not None else jnp.arange(s, dtype=jnp.int32)[None]
        q = apply_rope(q, jnp.broadcast_to(pos, (b, s)), rope_theta)
        k = apply_rope(k, jnp.broadcast_to(pos, (b, s)), rope_theta)
    if window is not None and causal and cross_kv is None:
        out = _sliding(q, k, v, window=window, q_chunk=q_chunk)
    else:
        out = _flash(q, k, v, causal=causal and cross_kv is None,
                     prefix_len=prefix_len, q_chunk=q_chunk, kv_chunk=kv_chunk)
    out = out.reshape(b, s, num_heads * head_dim) @ params["wo"]
    if return_kv:
        return out, (k, v)
    return out


# ---------------------------------------------------------------------------
# Decode (serve) path
# ---------------------------------------------------------------------------

def init_kv_cache(batch: int, max_len: int, num_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16) -> dict:
    return {
        "k": jnp.zeros((batch, max_len, num_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, num_kv_heads, head_dim), dtype),
    }


def decode_attention(params, x, cache, pos, *, num_heads: int,
                     num_kv_heads: int, head_dim: int,
                     rope_theta: Optional[float] = 1e4,
                     window: Optional[int] = None,
                     seq_shard_axis: Optional[str] = None,
                     cross: bool = False) -> tuple[jnp.ndarray, dict]:
    """One-token decode.  x: (B, 1, D); cache k/v: (B, S, KV, hd); ``pos``:
    scalar current position (number of tokens already cached).

    ``seq_shard_axis``: if set, k/v are sequence-sharded over that mesh axis
    and partial attention is LSE-combined with psums (long_500k path); the
    caller must run this inside shard_map.
    """
    b = x.shape[0]
    q, k_new, v_new = _project_qkv(params, x, x, num_heads, num_kv_heads, head_dim)
    if rope_theta is not None:
        posv = jnp.full((b, 1), pos, jnp.int32)
        q = apply_rope(q, posv, rope_theta)
        k_new = apply_rope(k_new, posv, rope_theta)

    if cross:
        # Fixed (precomputed) encoder K/V: attend over everything, no write.
        out = _cache_attn(q, cache["k"], cache["v"], pos, None)
        out = out.reshape(b, 1, num_heads * head_dim) @ params["wo"]
        return out, cache

    if seq_shard_axis is None:
        k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), pos, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), pos, axis=1)
        out = _cache_attn(q, k, v, pos, window)
        new_cache = {"k": k, "v": v}
    else:
        # Sequence-sharded cache: run the LSE-combined attention inside a
        # shard_map that is manual over the seq axis only ('model' and batch
        # sharding stay under the automatic partitioner).  The mesh comes from
        # the ambient compat.use_mesh context.  Each shard learns its own
        # index from a P(ax)-sharded iota instead of lax.axis_index
        # (DESIGN.md Sec. 2: partial-manual regions avoid axis_index).
        ax = seq_shard_axis
        mesh = compat.active_mesh()
        if mesh is None:
            raise RuntimeError(
                "sequence-sharded decode needs an ambient mesh -- wrap the "
                "call in `with repro.compat.use_mesh(mesh):`")
        n_shards = dict(zip(mesh.axis_names, mesh.axis_sizes))[ax]
        shard_ids = jnp.arange(n_shards, dtype=jnp.int32)
        kv_spec = P(None, ax, None, None)
        fn = functools.partial(_sharded_cache_attn, axis=ax, window=window)
        out, new_cache = compat.shard_map(
            fn,
            in_specs=(P(), P(), P(), {"k": kv_spec, "v": kv_spec}, P(), P(ax)),
            out_specs=(P(), {"k": kv_spec, "v": kv_spec}),
            axis_names={ax}, check_vma=False,
        )(q, k_new, v_new, {"k": cache["k"], "v": cache["v"]}, pos, shard_ids)
    out = out.reshape(b, 1, num_heads * head_dim) @ params["wo"]
    return out, new_cache


def _cache_attn(q, k, v, pos, window):
    b, _, h, hd = q.shape
    kv = k.shape[2]
    rep = h // kv
    sk = k.shape[1]
    qg = q.reshape(b, 1, kv, rep, hd)
    s_ = jnp.einsum("bqgrd,bkgd->bqgrk", qg.astype(jnp.float32), k.astype(jnp.float32)) * (hd ** -0.5)
    kpos = jnp.arange(sk)
    valid = kpos <= pos
    if window is not None:
        valid &= kpos > pos - window
    s_ = jnp.where(valid[None, None, None, None, :], s_, NEG_INF)
    p = jax.nn.softmax(s_, axis=-1)
    out = jnp.einsum("bqgrk,bkgd->bqgrd", p, v.astype(jnp.float32))
    return out.reshape(b, 1, h, hd).astype(q.dtype)


def _sharded_cache_attn(q, k_new, v_new, cache, pos, shard_id, *, axis: str,
                        window):
    """KV cache sharded over ``axis`` along the sequence dim; partial
    softmax per shard combined with max/sum psums (2 scalars per head).
    ``shard_id``: (1,) int32 -- this shard's index along ``axis``."""
    b, _, h, hd = q.shape
    kv = k_new.shape[2]
    rep = h // kv
    k_loc, v_loc = cache["k"], cache["v"]
    s_loc = k_loc.shape[1]
    my = shard_id[0]
    # The new token's kv is written into the shard that owns position `pos`.
    owner = pos // s_loc
    local_off = pos - owner * s_loc
    is_owner = (my == owner)
    k_upd = jax.lax.dynamic_update_slice_in_dim(k_loc, k_new.astype(k_loc.dtype), local_off, axis=1)
    v_upd = jax.lax.dynamic_update_slice_in_dim(v_loc, v_new.astype(v_loc.dtype), local_off, axis=1)
    k_loc = jnp.where(is_owner, k_upd, k_loc)
    v_loc = jnp.where(is_owner, v_upd, v_loc)

    qg = q.reshape(b, 1, kv, rep, hd)
    s_ = jnp.einsum("bqgrd,bkgd->bqgrk", qg.astype(jnp.float32),
                    k_loc.astype(jnp.float32)) * (hd ** -0.5)
    kpos = my * s_loc + jnp.arange(s_loc)
    valid = kpos <= pos
    if window is not None:
        valid &= kpos > pos - window
    s_ = jnp.where(valid[None, None, None, None, :], s_, NEG_INF)
    m_loc = jnp.max(s_, axis=-1)                         # (B,1,KV,rep)
    m_glob = jax.lax.pmax(m_loc, axis)
    p = jnp.exp(s_ - m_glob[..., None])
    l_loc = jnp.sum(p, axis=-1)
    o_loc = jnp.einsum("bqgrk,bkgd->bqgrd", p, v_loc.astype(jnp.float32))
    l_glob = jax.lax.psum(l_loc, axis)
    o_glob = jax.lax.psum(o_loc, axis)
    out = (o_glob / jnp.maximum(l_glob[..., None], 1e-30)).reshape(b, 1, h, hd)
    return out.astype(q.dtype), {"k": k_loc, "v": v_loc}
