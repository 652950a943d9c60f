"""Plain reference of a Mamba-2 language model (SSD block, arXiv:2405.21060)
as the configuration states it: token embedding, ``depth`` residual
blocks ``x + mixer(rmsnorm(x))``, a final RMSNorm and an untied output
head over the vocabulary.  Straight ``jax.numpy``; nothing of the program.

The mixer: one input projection to (z, x, B, C, dt); a depthwise causal
convolution of ``d_conv`` taps with SiLU over (x, B, C); dt = softplus(dt
+ dt_bias); A = −exp(A_log); the selective state space recurrence, token
by token (no chunking, so it shares no algorithm with the chunked scan),

    h_t = exp(dt_t·A)·h_{t−1} + dt_t·x_t ⊗ B_t,   y_t = h_t·C_t + D·x_t,

then a gated RMSNorm ``rmsnorm(y · silu(z))`` and the output projection.

Classification rides on next-token prediction: each example's first
``seq_len`` features are binned into ``vocab`` ids by
``floor((tanh(x/4) + 1)/2 · vocab)`` (clipped), the targets are the next
tokens with the class id last, and accuracy reads the last position's
argmax over the class ids.

Weights come from the row seed as the configuration documents: from
``jax.random.key(seed)`` eight keys (embedding 0, head 1, layers 2); the
embedding N(0, 0.02²) over the vocabulary padded to a multiple of 128,
dense matrices N(0, 1/fan_in), conv taps N(0, 0.1²), A_log = log of
``1..16`` spread over the heads, D = 1, biases 0, norm scales 1.  Each
layer's key splits four ways (input projection, conv, output projection).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_PAD = 128


def sizes(cfg: dict) -> dict:
    d = int(cfg["hidden"])
    d_in = int(cfg["expand"]) * d
    n, p = int(cfg["d_state"]), int(cfg["head_dim"])
    g = int(cfg["n_groups"])
    h = d_in // p
    conv_ch = d_in + 2 * g * n
    return dict(d=d, d_in=d_in, n=n, p=p, g=g, h=h, conv_ch=conv_ch,
                proj=2 * d_in + 2 * g * n + h, taps=int(cfg["d_conv"]),
                vocab=int(cfg["vocab"]), seq=int(cfg["seq_len"]),
                layers=int(cfg["depth"]),
                vocab_pad=-(-int(cfg["vocab"]) // VOCAB_PAD) * VOCAB_PAD)


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _layer(key, s):
    k_in, k_conv, k_out, _ = jax.random.split(key, 4)
    return {
        "ln": {"scale": jnp.ones((s["d"],), jnp.float32)},
        "mixer": {
            "in_proj": _normal(k_in, (s["d"], s["proj"]),
                               1 / math.sqrt(s["d"])),
            "conv_w": _normal(k_conv, (s["taps"], s["conv_ch"]), 0.1),
            "conv_b": jnp.zeros((s["conv_ch"],), jnp.float32),
            "A_log": jnp.log(jnp.linspace(1.0, 16.0, s["h"])),
            "D": jnp.ones((s["h"],), jnp.float32),
            "dt_bias": jnp.zeros((s["h"],), jnp.float32),
            "norm": {"scale": jnp.ones((s["d_in"],), jnp.float32)},
            "out_proj": _normal(k_out, (s["d_in"], s["d"]),
                                1 / math.sqrt(s["d_in"])),
        },
    }


def init(cfg: dict, seed: int):
    s = sizes(cfg)
    keys = jax.random.split(jax.random.key(seed), 8)
    layers = [_layer(k, s) for k in jax.random.split(keys[2], s["layers"])]
    return {
        "embed": {"table": _normal(keys[0], (s["vocab_pad"], s["d"]),
                                   0.02)},
        "lm_head": _normal(keys[1], (s["d"], s["vocab_pad"]),
                           1 / math.sqrt(s["d"])),
        "final_norm": {"scale": jnp.ones((s["d"],), jnp.float32)},
        "layers": jax.tree_util.tree_map(lambda *a: jnp.stack(a), *layers),
    }


def tokenize(cfg: dict, x: np.ndarray, y: np.ndarray):
    """(tokens, next-token targets) of int32, binned in float64."""
    vocab, seq = int(cfg["vocab"]), int(cfg["seq_len"])
    xs = np.asarray(x[:, :seq], np.float64)
    bins = np.floor((np.tanh(xs / 4.0) + 1.0) * 0.5 * vocab)
    tok = np.clip(bins, 0, vocab - 1).astype(np.int32)
    lab = np.concatenate([tok[:, 1:], np.asarray(y, np.int32)[:, None]], 1)
    return tok, lab


def train_inputs(cfg: dict, data):
    """Per-example arrays a training batch gathers: (tokens, targets)."""
    tok, lab = tokenize(cfg, data.x, data.y)
    return jnp.asarray(tok), jnp.asarray(lab)


def test_inputs(cfg: dict, data):
    """(tokens, class labels) of the test split."""
    tok, _ = tokenize(cfg, data.x, data.y)
    return jnp.asarray(tok), jnp.asarray(data.y)


def _rmsnorm(x, scale, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + jnp.asarray(eps, x.dtype)) * scale


def _mixer(mp, h, s, prec):
    B, S, _ = h.shape
    d_in, n, g = s["d_in"], s["n"], s["g"]
    proj = jnp.matmul(h, mp["in_proj"], precision=prec)
    z = proj[..., :d_in]
    xbc = proj[..., d_in:d_in + s["conv_ch"]]
    dt = proj[..., d_in + s["conv_ch"]:]
    taps = s["taps"]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = mp["conv_b"] + sum(padded[:, i:i + S] * mp["conv_w"][i]
                              for i in range(taps))
    conv = jax.nn.silu(conv)
    xs = conv[..., :d_in].reshape(B, S, s["h"], s["p"])
    Bm = conv[..., d_in:d_in + g * n].reshape(B, S, g, n)
    Cm = conv[..., d_in + g * n:].reshape(B, S, g, n)
    rep = s["h"] // g
    Bm, Cm = jnp.repeat(Bm, rep, 2), jnp.repeat(Cm, rep, 2)   # (B,S,H,N)
    dt = jax.nn.softplus(dt + mp["dt_bias"])                  # (B,S,H)
    A = -jnp.exp(mp["A_log"])

    def step(state, t):
        xt, bt, ct, dtt = t
        state = (jnp.exp(dtt * A)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        yt = jnp.einsum("bhpn,bhn->bhp", state, ct, precision=prec)
        return state, yt

    state0 = jnp.zeros((B, s["h"], s["p"], n), h.dtype)
    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (xs, Bm, Cm, dt))
    _, y = jax.lax.scan(step, state0, seq)
    y = jnp.moveaxis(y, 0, 1) + xs * mp["D"][:, None]
    y = y.reshape(B, S, d_in)
    y = _rmsnorm(y * jax.nn.silu(z), mp["norm"]["scale"])
    return jnp.matmul(y, mp["out_proj"], precision=prec)


def logits(params, tok, cfg, prec):
    s = sizes(cfg)
    x = params["embed"]["table"][tok]
    for i in range(s["layers"]):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        x = x + _mixer(lp["mixer"], _rmsnorm(x, lp["ln"]["scale"]), s,
                       prec)
    x = _rmsnorm(x, params["final_norm"]["scale"])
    return jnp.matmul(x, params["lm_head"], precision=prec)[..., :s["vocab"]]


def loss(params, batch, w, cfg, prec):
    """Token cross-entropy, each sequence weighted by its example weight:
    Σ w·nll / max(Σ w, 1e-6) over every position."""
    tok, lab = batch
    z = logits(params, tok, cfg, prec)
    nll = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
        z, lab[..., None], -1)[..., 0]
    wt = jnp.broadcast_to(w[:, None], nll.shape).astype(nll.dtype)
    return jnp.sum(nll * wt) / jnp.maximum(jnp.sum(wt), 1e-6)


def accuracy(params, batch, cfg, prec):
    tok, y = batch
    z = logits(params, tok, cfg, prec)[:, -1, :int(cfg["classes"])]
    return jnp.mean((jnp.argmax(z, -1) == y).astype(jnp.float32))


def train_flops_per_example(cfg: dict) -> float:
    """Forward and backward of one sequence: 6 × the multiply-adds per
    token (forward once, gradients of weights and of inputs twice) × the
    sequence length.  Per token and layer: the input and output
    projections, the conv taps, and the recurrence's state update and
    read-out (2·H·P·N); once per token the output head over the true
    vocabulary."""
    s = sizes(cfg)
    per_layer = (s["d"] * s["proj"] + s["d_in"] * s["d"]
                 + s["taps"] * s["conv_ch"] + 2 * s["h"] * s["p"] * s["n"])
    return 6.0 * s["seq"] * (s["layers"] * per_layer + s["d"] * s["vocab"])
