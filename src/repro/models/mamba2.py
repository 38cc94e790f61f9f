"""Mamba-2 SSD (state-space duality) block [arXiv:2405.21060], TPU-adapted.

The selective state-space recurrence

    h_t = exp(dt_t * A) h_{t-1} + dt_t * B_t (x) x_t      (state: (N, P) per head)
    y_t = C_t . h_t + D * x_t

is computed with the SSD *chunked* algorithm: sequences are split into
chunks of Q tokens; within a chunk the contribution is an attention-like
masked matmul (MXU-friendly), across chunks a short ``lax.scan`` carries the
(B, H, N, P) state.  This is the paper's (Dao & Gu) blocked duality mapped
onto jnp einsums -- no Triton port, the TPU gets big dense matmuls.

Projections are kept separate (wz/wx/wB/wC/wdt) rather than fused so tensor
parallelism can shard d_inner cleanly; depthwise causal convs (width 4) run
over the x/B/C streams as in the reference implementation.

Decode is the O(1) recurrence with a conv tail cache -- no attention, no KV
cache, which is why mamba2/jamba run the long_500k shape natively.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import rmsnorm


def mamba_params(make, prefix: str, *, d_model: int, d_inner: int,
                 ssm_state: int, num_heads: int, conv_width: int = 4):
    return {
        "wz": make(f"{prefix}.wz", (d_model, d_inner), P(None, "model")),
        "wx": make(f"{prefix}.wx", (d_model, d_inner), P(None, "model")),
        "wB": make(f"{prefix}.wB", (d_model, ssm_state), P(None, None)),
        "wC": make(f"{prefix}.wC", (d_model, ssm_state), P(None, None)),
        "wdt": make(f"{prefix}.wdt", (d_model, num_heads), P(None, None)),
        "conv_x": make(f"{prefix}.conv_x", (conv_width, d_inner), P(None, "model"), ("normal", 0.1)),
        "conv_B": make(f"{prefix}.conv_B", (conv_width, ssm_state), P(None, None), ("normal", 0.1)),
        "conv_C": make(f"{prefix}.conv_C", (conv_width, ssm_state), P(None, None), ("normal", 0.1)),
        "A_log": make(f"{prefix}.A_log", (num_heads,), P(None), "zeros"),
        "D": make(f"{prefix}.D", (num_heads,), P(None), "ones"),
        "dt_bias": make(f"{prefix}.dt_bias", (num_heads,), P(None), "zeros"),
        "norm": make(f"{prefix}.norm", (d_inner,), P("model"), "ones"),
        "out": make(f"{prefix}.out", (d_inner, d_model), P("model", None)),
    }


def _causal_conv(x: jnp.ndarray, kernel: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv.  x: (B, L, C); kernel: (W, C)."""
    w = kernel.shape[0]
    xp = jnp.pad(x, ((0, 0), (w - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(w):
        out = out + xp[:, i : i + x.shape[1]].astype(jnp.float32) * kernel[i].astype(jnp.float32)
    return out.astype(x.dtype)


def _ssd_chunked(x, b_in, c_in, dt, a, *, chunk: int,
                 h0: Optional[jnp.ndarray] = None):
    """Chunked SSD scan.

    x: (B, L, H, P); b_in/c_in: (B, L, N); dt: (B, L, H) (>0); a: (H,) (<0).
    Returns y: (B, L, H, P) and final state (B, H, N, P).
    """
    bsz, l, h, p = x.shape
    n = b_in.shape[-1]
    q = min(chunk, l)
    pad = (-l) % q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        b_in = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0)))
        c_in = jnp.pad(c_in, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    nc = x.shape[1] // q
    xs = x.reshape(bsz, nc, q, h, p)
    bs = b_in.reshape(bsz, nc, q, n)
    cs = c_in.reshape(bsz, nc, q, n)
    dts = dt.reshape(bsz, nc, q, h).astype(jnp.float32)

    da = dts * a  # (B, nc, Q, H)   (negative)
    cum = jnp.cumsum(da, axis=2)
    dec_end = jnp.exp(cum[:, :, -1:, :] - cum)        # decay from t to chunk end
    lam = jnp.exp(cum[:, :, -1, :])                    # (B, nc, H) whole-chunk decay

    # Per-chunk injected state: S_c = sum_i dec_end_i dt_i B_i (x) x_i.
    s_chunk = jnp.einsum("bcqh,bcqn,bcqhp->bchnp",
                         dec_end * dts, bs.astype(jnp.float32), xs.astype(jnp.float32))

    def scan_body(hprev, inp):
        lam_c, s_c = inp  # (B, H), (B, H, N, P)
        return lam_c[..., None, None] * hprev + s_c, hprev

    h_init = h0 if h0 is not None else jnp.zeros((bsz, h, n, p), jnp.float32)
    h_last, h_enter = jax.lax.scan(
        scan_body, h_init,
        (lam.transpose(1, 0, 2), s_chunk.transpose(1, 0, 2, 3, 4)))
    h_enter = h_enter.transpose(1, 0, 2, 3, 4)  # (B, nc, H, N, P): state entering chunk

    # Intra-chunk (masked attention-like) term.
    rel = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # (B,nc,Qt,Qi,H) = cum_t - cum_i
    mask = jnp.tril(jnp.ones((q, q), bool))
    # Mask BEFORE the exp: above the diagonal rel > 0 grows with the chunk
    # and overflows to inf (about 88 nats of decay in f32), and the
    # gradient of where(mask, inf, 0) is 0 * inf = NaN.
    gate = jnp.exp(jnp.where(mask[None, None, :, :, None], rel, -jnp.inf))
    scores = jnp.einsum("bcqn,bcin->bcqi", cs.astype(jnp.float32), bs.astype(jnp.float32))
    scores = scores[..., None] * gate * dts[:, :, None, :, :]  # (B,nc,Qt,Qi,H)
    y_intra = jnp.einsum("bcqih,bcihp->bcqhp", scores, xs.astype(jnp.float32))

    # Inter-chunk term: y_inter(t) = exp(cum_t) * C_t . h_enter.
    y_inter = jnp.einsum("bcqn,bchnp,bcqh->bcqhp",
                         cs.astype(jnp.float32), h_enter, jnp.exp(cum))
    y = (y_intra + y_inter).reshape(bsz, nc * q, h, p)[:, :l]
    return y.astype(x.dtype), h_last


def ssd_reference(x, b_in, c_in, dt, a):
    """Naive O(L) recurrence oracle (tests only)."""
    bsz, l, h, p = x.shape
    n = b_in.shape[-1]

    def step(hprev, t):
        da = dt[:, t] * a  # (B, H)
        hnew = jnp.exp(da)[..., None, None] * hprev + jnp.einsum(
            "bh,bn,bhp->bhnp", dt[:, t], b_in[:, t], x[:, t])
        y = jnp.einsum("bn,bhnp->bhp", c_in[:, t], hnew)
        return hnew, y

    h0 = jnp.zeros((bsz, h, n, p), jnp.float32)
    hfin, ys = jax.lax.scan(step, h0, jnp.arange(l))
    return ys.transpose(1, 0, 2, 3), hfin


def mamba_block(params, x, *, num_heads: int, head_dim: int, ssm_state: int,
                chunk: int = 256, return_state: bool = False):
    """Full-sequence mamba2 block.  x: (B, L, D)."""
    bsz, l, d = x.shape
    z = x @ params["wz"]
    x_raw = x @ params["wx"]
    b_raw = x @ params["wB"]
    c_raw = x @ params["wC"]
    xin = jax.nn.silu(_causal_conv(x_raw, params["conv_x"]))
    b_in = jax.nn.silu(_causal_conv(b_raw, params["conv_B"]))
    c_in = jax.nn.silu(_causal_conv(c_raw, params["conv_C"]))
    dt = jax.nn.softplus((x @ params["wdt"]).astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["A_log"].astype(jnp.float32))
    xh = xin.reshape(bsz, l, num_heads, head_dim)
    y, h_last = _ssd_chunked(xh, b_in, c_in, dt, a, chunk=chunk)
    y = y + params["D"].astype(jnp.float32)[None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(bsz, l, num_heads * head_dim).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), params["norm"])
    out = y @ params["out"]
    if return_state:
        w = params["conv_x"].shape[0]
        tail = lambda r: r[:, -(w - 1):] if l >= w - 1 else jnp.pad(r, ((0, 0), (w - 1 - l, 0), (0, 0)))
        state = {"h": h_last, "conv_x": tail(x_raw), "conv_B": tail(b_raw),
                 "conv_C": tail(c_raw)}
        return out, state
    return out


# ---------------------------------------------------------------------------
# Decode path
# ---------------------------------------------------------------------------

def init_mamba_cache(batch: int, *, num_heads: int, head_dim: int,
                     ssm_state: int, conv_width: int = 4, dtype=jnp.float32):
    return {
        "h": jnp.zeros((batch, num_heads, ssm_state, head_dim), jnp.float32),
        "conv_x": jnp.zeros((batch, conv_width - 1, num_heads * head_dim), dtype),
        "conv_B": jnp.zeros((batch, conv_width - 1, ssm_state), dtype),
        "conv_C": jnp.zeros((batch, conv_width - 1, ssm_state), dtype),
    }


def _conv_step(cache_tail, new, kernel):
    """cache_tail: (B, W-1, C); new: (B, C). Returns (out (B,C), new_tail)."""
    full = jnp.concatenate([cache_tail, new[:, None]], axis=1)  # (B, W, C)
    out = jnp.sum(full.astype(jnp.float32) * kernel[None].astype(jnp.float32), axis=1)
    return out.astype(new.dtype), full[:, 1:]


def mamba_decode_step(params, x, cache, *, num_heads: int, head_dim: int,
                      ssm_state: int) -> tuple[jnp.ndarray, dict]:
    """One-token decode.  x: (B, 1, D)."""
    bsz = x.shape[0]
    xt = x[:, 0]
    z = xt @ params["wz"]
    xin_raw = xt @ params["wx"]
    b_raw = xt @ params["wB"]
    c_raw = xt @ params["wC"]
    xin, tail_x = _conv_step(cache["conv_x"], xin_raw, params["conv_x"])
    b_in, tail_b = _conv_step(cache["conv_B"], b_raw, params["conv_B"])
    c_in, tail_c = _conv_step(cache["conv_C"], c_raw, params["conv_C"])
    xin = jax.nn.silu(xin)
    b_in = jax.nn.silu(b_in).astype(jnp.float32)
    c_in = jax.nn.silu(c_in).astype(jnp.float32)
    dt = jax.nn.softplus((xt @ params["wdt"]).astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["A_log"].astype(jnp.float32))
    xh = xin.reshape(bsz, num_heads, head_dim).astype(jnp.float32)
    h = cache["h"]
    h = jnp.exp(dt * a)[..., None, None] * h + jnp.einsum("bh,bn,bhp->bhnp", dt, b_in, xh)
    y = jnp.einsum("bn,bhnp->bhp", c_in, h)
    y = y + params["D"].astype(jnp.float32)[None, :, None] * xh
    y = y.reshape(bsz, num_heads * head_dim).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), params["norm"])
    out = (y @ params["out"])[:, None]
    return out, {"h": h, "conv_x": tail_x, "conv_B": tail_b, "conv_C": tail_c}
