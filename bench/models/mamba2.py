"""Plain reference of a Mamba-2 language model (SSD block, arXiv:2405.21060)
as the configuration states it: token embedding, ``depth`` residual
blocks ``x + mixer(rmsnorm(x))``, a final RMSNorm and an untied output
head over the vocabulary.  Straight ``jax.numpy``; nothing of the program.

The mixer: one input projection to (z, x, B, C, dt); a depthwise causal
convolution of ``d_conv`` taps with SiLU over (x, B, C); dt = softplus(dt
+ dt_bias); A = −exp(A_log); the selective state space recurrence, token
by token (no SSD chunking, so it shares no algorithm with the chunked
scan),

    h_t = exp(dt_t·A)·h_{t−1} + dt_t·x_t ⊗ B_t,   y_t = h_t·C_t + D·x_t,

then a gated RMSNorm ``rmsnorm(y · silu(z))`` and the output projection.
The positions are scanned in blocks of about √S, each block under
``jax.checkpoint``, so the backward pass keeps one state a block and
recomputes the block's states, not one state a token; the arithmetic is
the same token-by-token recurrence.  The layers are scanned, each under
``jax.checkpoint``: the backward pass keeps each layer's input and
recomputes one layer at a time.

The task is next-token prediction on token text (the configuration's
``data`` kind ``markov_tokens``, ``workload.make_data``): each example is
``seq_len + 1`` tokens, the inputs are its first ``seq_len`` and the
targets its last ``seq_len``; the loss is the token cross-entropy and the
accuracy top-1 next-token accuracy over every position.

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


def train_inputs(cfg: dict, data):
    """Per-example arrays a batch gathers: (inputs, next-token targets),
    the first and the last ``seq_len`` tokens of each example."""
    kind = (cfg.get("data") or {}).get("kind")
    if kind != "markov_tokens":
        raise ValueError(f"the Mamba-2 reference trains on token text "
                         f"(data kind 'markov_tokens'), not {kind!r}")
    seq = int(cfg["seq_len"])
    x = jnp.asarray(data.x, jnp.int32)
    return x[:, :seq], x[:, 1:seq + 1]


test_inputs = train_inputs


def _rmsnorm(x, scale, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + jnp.asarray(eps, x.dtype)) * scale


def recurrence(A, xs, Bm, Cm, dt, prec):
    """y_t = h_t·C_t with h_t = exp(dt_t·A)·h_{t−1} + dt_t·x_t ⊗ B_t, token
    by token, from h_0 = 0.  ``xs`` is (B, S, H, P), ``Bm`` and ``Cm``
    (B, S, G, N) with the heads split evenly over the G groups, ``dt``
    (B, S, H).  The positions are scanned in blocks of ⌈√S⌉, each under
    ``jax.checkpoint``; the last block is padded at its end, after every
    real position, and its padded outputs dropped."""
    b, S, H, P = xs.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    L = math.isqrt(S - 1) + 1
    nb = -(-S // L)

    def step(state, t):
        xt, bt, ct, dtt = t
        bt, ct = jnp.repeat(bt, rep, 1), jnp.repeat(ct, rep, 1)  # (B,H,N)
        state = (jnp.exp(dtt * A)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        yt = jnp.einsum("bhpn,bhn->bhp", state, ct, precision=prec)
        return state, yt

    @jax.checkpoint
    def run_block(state, blk):
        return jax.lax.scan(step, state, blk)

    def blocks(a):
        a = jnp.moveaxis(a, 1, 0)
        a = jnp.pad(a, [(0, nb * L - S)] + [(0, 0)] * (a.ndim - 1))
        return a.reshape((nb, L) + a.shape[1:])

    state0 = jnp.zeros((b, H, P, N), xs.dtype)
    _, y = jax.lax.scan(run_block, state0,
                        tuple(blocks(a) for a in (xs, Bm, Cm, dt)))
    return jnp.moveaxis(y.reshape((nb * L,) + y.shape[2:])[:S], 0, 1)


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
    dt = jax.nn.softplus(dt + mp["dt_bias"])                  # (B,S,H)
    A = -jnp.exp(mp["A_log"])
    y = recurrence(A, xs, Bm, Cm, dt, prec) + xs * mp["D"][:, None]
    y = y.reshape(B, S, d_in)
    y = _rmsnorm(y * jax.nn.silu(z), mp["norm"]["scale"])
    return jnp.matmul(y, mp["out_proj"], precision=prec)


def logits(params, tok, cfg, prec):
    s = sizes(cfg)

    @jax.checkpoint
    def layer(x, lp):
        return x + _mixer(lp["mixer"], _rmsnorm(x, lp["ln"]["scale"]), s,
                          prec), None

    x, _ = jax.lax.scan(layer, params["embed"]["table"][tok],
                        params["layers"])
    x = _rmsnorm(x, params["final_norm"]["scale"])
    return jnp.matmul(x, params["lm_head"], precision=prec)[..., :s["vocab"]]


def nll_sums(params, batch, w, cfg, prec):
    """(Σ w·nll, Σ w) over every position, each sequence weighted by its
    example weight: the two sums that blocks of a batch add up to, before
    ``weighted_mean`` divides them once."""
    tok, lab = batch
    z = logits(params, tok, cfg, prec)
    nll = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
        z, lab[..., None], -1)[..., 0]
    wt = jnp.broadcast_to(w[:, None], nll.shape).astype(nll.dtype)
    return jnp.sum(nll * wt), jnp.sum(wt)


def weighted_mean(s, n):
    """Σ w·nll / max(Σ w, 1e-6): the token cross-entropy."""
    return s / jnp.maximum(n, 1e-6)


def hits(params, batch, cfg, prec):
    """(correct top-1 next-token predictions, predictions made) over every
    position of a batch."""
    tok, lab = batch
    right = (jnp.argmax(logits(params, tok, cfg, prec), -1) == lab).astype(
        jnp.float32)
    return jnp.sum(right), right.size


def logits_per_example(cfg: dict) -> int:
    """Logits one sequence makes (positions × vocabulary): the reference's
    blocking rules size a pass by them."""
    return int(cfg["seq_len"]) * int(cfg["vocab"])


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
