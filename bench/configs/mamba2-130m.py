"""mamba2-130m at its published widths (state-spaces/mamba2-130m,
arXiv:2405.21060), trained on the mesh path (``bench/lib/mesh.py``).

What the harness takes from here: the program's model configuration built
from ``mamba2-130m.json``; the weights, made on the device from the seed in
the program's parameter layout (bf16, as served); the token batches; the
FLOP count behind ``step_mfu``; and the plain float32 reference of the
loss, with the fp8 control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

CONV = 4    # the program's depthwise convolution width (the source's d_conv)


def _check(cfg: dict):
    if cfg["d_conv"] != CONV or cfg["ngroups"] != 1 or cfg["d_intermediate"]:
        raise ValueError("the program's mamba2 layer has d_conv 4, one B/C "
                         "group and no MLP; this configuration asks otherwise")


def vocab_rows(cfg: dict) -> int:
    """The embedding's rows: the vocabulary padded up to a multiple of
    ``pad_vocab_size_multiple``, as the source's checkpoint has them."""
    m = cfg.get("pad_vocab_size_multiple", 1)
    return -(-cfg["vocab_size"] // m) * m


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for these sizes."""
    from repro.configs.base import BlockSpec, ModelConfig
    _check(cfg)
    return ModelConfig(
        name=cfg["name"], family="ssm", num_layers=cfg["n_layer"],
        d_model=cfg["d_model"], num_heads=1, num_kv_heads=1, d_ff=0,
        vocab_size=vocab_rows(cfg), pattern=(BlockSpec(kind="mamba"),),
        ssm_state=cfg["d_state"], ssm_expand=cfg["expand"],
        ssm_head_dim=cfg["headdim"], ssm_chunk=cfg["chunk_size"],
        tie_embeddings=cfg["tie_embeddings"], param_dtype=cfg["param_dtype"])


def _dims(cfg: dict):
    d, di = cfg["d_model"], cfg["expand"] * cfg["d_model"]
    return d, di, cfg["d_state"], di // cfg["headdim"], cfg["n_layer"]


def init_params(key, cfg: dict) -> dict:
    """Random weights from ``key`` in the program's layout (layers stacked
    on a leading axis), in ``param_dtype``; traced inside one jit by the
    caller."""
    d, di, n, h, nl = _dims(cfg)
    dtype = jnp.dtype(cfg["param_dtype"])
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return (std * jax.random.normal(next(ks), shape, jnp.float32)).astype(dtype)

    ones = lambda shape: jnp.ones(shape, dtype)
    dt = jnp.exp(jax.random.uniform(next(ks), (nl, h), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    mamba = {
        "wz": normal((nl, d, di), d ** -0.5),
        "wx": normal((nl, d, di), d ** -0.5),
        "wB": normal((nl, d, n), d ** -0.5),
        "wC": normal((nl, d, n), d ** -0.5),
        "wdt": normal((nl, d, h), d ** -0.5),
        "conv_x": normal((nl, CONV, di), 0.1),
        "conv_B": normal((nl, CONV, n), 0.1),
        "conv_C": normal((nl, CONV, n), 0.1),
        "A_log": jnp.log(jax.random.uniform(next(ks), (nl, h), jnp.float32,
                                            1.0, 16.0)).astype(dtype),
        "D": ones((nl, h)),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "norm": ones((nl, di)),
        "out": normal((nl, di, d), di ** -0.5),
    }
    return {"embed": normal((vocab_rows(cfg), d), 0.02),
            "blocks": {"pos0": {"pre_norm": {"scale": ones((nl, d))},
                                "mamba": mamba,
                                "mlp_norm": {"scale": ones((nl, d))}}},
            "final_norm": {"scale": ones((d,))}}


def make_batch(key, cfg: dict, workers: int, per_worker: int, seq: int):
    """Uniform tokens over the real ids (not the padding rows) and their
    next-token labels, (W, B, seq) int32 (copied from
    ``repro.launch.train.make_batch``)."""
    toks = jax.random.randint(key, (workers, per_worker, seq + 1), 0,
                              cfg["vocab_size"], jnp.int32)
    return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}


def flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs of forward + backward per trained token: 3 x the forward
    (copied from ``repro.launch.analytic._layer_token_flops``, mamba branch,
    plus the tied logits over every row), with the chunk the program
    computes at ``seq``."""
    d, di, n, h, nl = _dims(cfg)
    p, q = cfg["headdim"], min(cfg["chunk_size"], seq)
    layer = (2 * d * (2 * di + 2 * n + h)        # in projections
             + 2 * CONV * (di + 2 * n)            # convolutions
             + 2 * (q * n + q * h * p)            # intra-chunk dual form
             + 2 * 2 * n * h * p                  # chunk states + inter-chunk
             + 2 * di * d)                        # out projection
    return 3.0 * (nl * layer + 2 * d * vocab_rows(cfg))


def flops_per_round(cfg: dict, traffic: dict) -> float:
    w = traffic["mesh"][0]
    tokens = w * traffic["per_worker_batch"] * traffic["seq"]
    return flops_per_token(cfg, traffic["seq"]) * tokens


# ---------------------------------------------------------------------------
# The plain reference: float32, every matmul at "highest" (set by the caller).
# The control rounds every matmul's operands to fp8 (e4m3, per-tensor scale)
# and their gradients to e5m2: the precision below the bf16 this
# configuration states.
# ---------------------------------------------------------------------------

def _fp8(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def _q_fwd8(x):
    return _fp8(x, jnp.float8_e4m3fn)


_q_fwd8.defvjp(lambda x: (_fp8(x, jnp.float8_e4m3fn), None),
               lambda _, g: (_fp8(g, jnp.float8_e5m2),))


@jax.custom_vjp
def _q_bwd8(x):
    return x


_q_bwd8.defvjp(lambda x: (x, None), lambda _, g: (_fp8(g, jnp.float8_e5m2),))


def _matmul(policy: str):
    if policy == "f32":
        return lambda x, w: x @ w
    if policy == "control":
        return lambda x, w: _q_bwd8(_q_fwd8(x) @ _q_fwd8(w))
    raise ValueError(policy)


CONTROL = "control"


def _rms(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _conv(x, k):
    """Causal depthwise convolution over the sequence: x (B, L, C), k (4, C)."""
    xp = jnp.pad(x, ((0, 0), (CONV - 1, 0), (0, 0)))
    return sum(xp[:, i:i + x.shape[1]] * k[i] for i in range(CONV))


def _mixer(m, x, cfg, mm):
    """One Mamba-2 mixer, its SSD in the quadratic (attention-like) form
    over the whole sequence: y_t = sum_{s<=t} (C_t . B_s)
    exp(sum_{s<r<=t} dt_r A) dt_s x_s + D x_t."""
    b, l, _ = x.shape
    _, di, n, h, _ = _dims(cfg)
    p = cfg["headdim"]
    z = mm(x, m["wz"])
    xs = jax.nn.silu(_conv(mm(x, m["wx"]), m["conv_x"])).reshape(b, l, h, p)
    bs = jax.nn.silu(_conv(mm(x, m["wB"]), m["conv_B"]))
    cs = jax.nn.silu(_conv(mm(x, m["wC"]), m["conv_C"]))
    dt = jax.nn.softplus(mm(x, m["wdt"]) + m["dt_bias"])          # (B, L, H)
    a = -jnp.exp(m["A_log"])
    cum = jnp.cumsum(dt * a, axis=1)                                # (B, L, H)
    seg = cum[:, :, None, :] - cum[:, None, :, :]                   # (B, t, s, H)
    causal = jnp.tril(jnp.ones((l, l), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("btn,bsn->bts", cs, bs)
    weights = cb[..., None] * decay * dt[:, None, :, :]             # (B, t, s, H)
    y = jnp.einsum("btsh,bshp->bthp", weights, xs) + m["D"][:, None] * xs
    y = _rms(y.reshape(b, l, di) * jax.nn.silu(z), m["norm"])
    return mm(y, m["out"])


def ref_loss(params, tokens, labels, cfg: dict, policy: str = "f32"):
    """Mean next-token cross-entropy of the plain model; ``params`` float32
    in the program's layout (the unused ``mlp_norm`` included)."""
    mm = _matmul(policy)
    blocks = params["blocks"]["pos0"]

    def layer(x, lp):
        h = _rms(x, lp["pre_norm"]["scale"])
        return x + _mixer(lp["mamba"], h, cfg, mm), None

    x = params["embed"][tokens]
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, blocks)
    x = _rms(x, params["final_norm"]["scale"])
    logits = mm(x, params["embed"].T)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)
