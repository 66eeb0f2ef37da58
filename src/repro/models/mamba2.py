"""Mamba-2 SSD (state-space duality) — mamba2-780m, and the backbone of the
zamba2 hybrid.

Chunked SSD algorithm (Dao & Gu 2024, arXiv:2405.21060): split the sequence
into chunks of length Q; within a chunk the recurrence is computed as a
masked (attention-like) matmul — the "duality" — and across chunks a short
``lax.scan`` carries the (heads, headdim, d_state) recurrent state. Decode
is an O(1) single-token state update, so a 512k context costs the same per
token as a 4k one (this is why the SSM archs run the ``long_500k`` cell).

Layout: x is split into ``nh`` heads of size ``hp = d_inner // nh``;
B and C (input/output projections of the state space) are shared across
heads within a group (we use a single group, as mamba2-780m does).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_mixer(rng, cfg, dt):
    d, din, N, nh = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    conv_dim = din + 2 * N
    ks = jax.random.split(rng, 4)
    return {
        # [z (gate), x, B, C, dt] fused input projection
        "in_proj": L.dense_init(ks[0], (d, 2 * din + 2 * N + nh), dt),
        "conv_w": L.dense_init(ks[1], (cfg.conv_width, conv_dim), dt,
                               scale=cfg.conv_width ** -0.5),
        "conv_b": jnp.zeros((conv_dim,), dt),
        "A_log": jnp.zeros((nh,), jnp.float32),      # A = -exp(A_log)
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "norm": jnp.ones((din,), dt),                # gated RMSNorm scale
        "out_proj": L.dense_init(ks[2], (din, d), dt),
    }


def mixer_specs(cfg, rules):
    d, din, N, nh = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    conv_dim = din + 2 * N
    return {
        "in_proj": P(rules.fsdp_for(d), rules.tp_for(2 * din + 2 * N + nh)),
        "conv_w": P(None, rules.tp_for(conv_dim)),
        "conv_b": P(rules.tp_for(conv_dim)),
        "A_log": P(rules.tp_for(nh)), "D": P(rules.tp_for(nh)),
        "dt_bias": P(rules.tp_for(nh)),
        "norm": P(rules.tp_for(din)),
        "out_proj": P(rules.tp_for(din), rules.fsdp_for(d)),
    }


def init_layer(rng, cfg, dt):
    return {"mixer": init_mixer(rng, cfg, dt),
            "ln": jnp.ones((cfg.d_model,), dt)}


def layer_specs(cfg, rules):
    return {"mixer": mixer_specs(cfg, rules), "ln": P(None)}


def init_params(cfg, rng):
    dt = cfg.pdtype()
    r_embed, r_layers = jax.random.split(rng)
    rngs = jax.random.split(r_layers, cfg.n_layers)
    return {"embed": L.init_embed(r_embed, cfg, dt),
            "layers": jax.vmap(partial(init_layer, cfg=cfg, dt=dt))(rngs),
            "ln_f": jnp.ones((cfg.d_model,), dt)}


def param_specs(cfg, rules):
    lsp = layer_specs(cfg, rules)
    stacked = jax.tree.map(lambda s: P(None, *s), lsp,
                           is_leaf=lambda x: isinstance(x, P))
    return {"embed": L.specs_embed(cfg, rules),
            "layers": stacked, "ln_f": P(None)}


# ---------------------------------------------------------------------------
# SSD core (chunked scan) — also the jnp oracle for models/kernels/ssd_scan
# ---------------------------------------------------------------------------

def _split_proj(params, cfg, u):
    """u: (B,S,d) -> z,(B,S,din) x,(B,S,din) Bm/Cm,(B,S,N) dt,(B,S,nh)."""
    din, N, nh = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    zxbcdt = u @ params["in_proj"]
    z, xBC, dt = jnp.split(zxbcdt, [din, 2 * din + 2 * N], axis=-1)
    return z, xBC, dt


def _causal_conv(params, cfg, xBC, conv_state=None):
    """Depthwise causal conv over the sequence; returns (out, new_state)."""
    W = cfg.conv_width
    if conv_state is None:
        pad = jnp.zeros(xBC.shape[:-2] + (W - 1, xBC.shape[-1]), xBC.dtype)
    else:
        pad = conv_state
    xp = jnp.concatenate([pad, xBC], axis=-2)             # (B, W-1+S, C)
    new_state = xp[..., -(W - 1):, :]
    out = sum(xp[..., i:i + xBC.shape[-2], :] * params["conv_w"][i]
              for i in range(W))
    return jax.nn.silu(out + params["conv_b"]), new_state


def ssd_chunked(x, dt, A, Bm, Cm, D, chunk: int, initial_state=None):
    """Chunked state-space duality scan.

    x: (B,S,nh,hp)  dt: (B,S,nh)  A: (nh,)  Bm/Cm: (B,S,N)  D: (nh,)
    Returns y: (B,S,nh,hp), final_state: (B,nh,hp,N).
    """
    Bsz, S, nh, hp = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    nc = S // Q
    dtA = dt * A[None, None, :]                            # (B,S,nh)

    xc = x.reshape(Bsz, nc, Q, nh, hp)
    dtc = dt.reshape(Bsz, nc, Q, nh)
    dtAc = dtA.reshape(Bsz, nc, Q, nh)
    Bc = Bm.reshape(Bsz, nc, Q, N)
    Cc = Cm.reshape(Bsz, nc, Q, N)

    seg = jnp.cumsum(dtAc, axis=2)                         # (B,nc,Q,nh)
    # intra-chunk "attention" matrix: L[i,j] = exp(seg_i - seg_j) * dt_j, i>=j
    diff = seg[:, :, :, None, :] - seg[:, :, None, :, :]   # (B,nc,Q,Q,nh)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    Lmat = jnp.where(causal[None, None, :, :, None],
                     jnp.exp(diff), 0.0)                   # (B,nc,Q,Q,nh)
    CB = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)             # (B,nc,Q,Q)
    M = CB[..., None] * Lmat * dtc[:, :, None, :, :]       # (B,nc,Q,Q,nh)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", M, xc)

    # per-chunk state contribution: sum_j exp(seg_Q - seg_j) dt_j B_j x_j
    decay_out = jnp.exp(seg[:, :, -1:, :] - seg)           # (B,nc,Q,nh)
    state_in = jnp.einsum("bcjn,bcjh,bcjhp->bchpn",
                          Bc, dtc * decay_out, xc)         # (B,nc,nh,hp,N)
    chunk_decay = jnp.exp(seg[:, :, -1, :])                # (B,nc,nh)

    def scan_body(s, inp):
        contrib, dec = inp                                 # (B,nh,hp,N),(B,nh)
        s_out = s
        s = s * dec[..., None, None] + contrib
        return s, s_out

    s0 = (initial_state if initial_state is not None
          else jnp.zeros((Bsz, nh, hp, N), x.dtype))
    final, states = jax.lax.scan(
        scan_body,
        s0.astype(jnp.float32),
        (state_in.transpose(1, 0, 2, 3, 4).astype(jnp.float32),
         chunk_decay.transpose(1, 0, 2)))
    states = states.transpose(1, 0, 2, 3, 4)               # (B,nc,nh,hp,N)

    # inter-chunk output: C_i exp(seg_i) @ incoming state
    y_inter = jnp.einsum("bcin,bcih,bchpn->bcihp",
                         Cc, jnp.exp(seg).astype(jnp.float32),
                         states).astype(x.dtype)
    y = (y_intra + y_inter).reshape(Bsz, S, nh, hp)
    y = y + x * D[None, None, :, None]
    return y.astype(x.dtype), final.astype(x.dtype)


def mixer_forward(params, cfg, u, rules=None, state=None):
    """Full-sequence mixer (train/prefill). Returns (y, (conv_st, ssm_st))."""
    B, S, _ = u.shape
    din, N, nh = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    hp = din // nh
    z, xBC, dt = _split_proj(params, cfg, u)
    xBC, conv_st = _causal_conv(params, cfg, xBC)
    x, Bm, Cm = jnp.split(xBC, [din, din + N], axis=-1)
    x = L.shard(x.reshape(B, S, nh, hp), P("DP", None, "TP", None), rules)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"])              # (B,S,nh)
    A = -jnp.exp(params["A_log"])
    y, ssm_st = ssd_chunked(x, dt, A, Bm.astype(jnp.float32),
                            Cm.astype(jnp.float32), params["D"],
                            cfg.ssm_chunk, initial_state=state)
    y = y.reshape(B, S, din)
    y = L.rmsnorm(y * jax.nn.silu(z), params["norm"])      # gated norm
    return y @ params["out_proj"], (conv_st, ssm_st)


def mixer_decode(params, cfg, u, conv_state, ssm_state):
    """O(1) single-token state update. u: (B,1,d)."""
    B = u.shape[0]
    din, N, nh = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    hp = din // nh
    z, xBC, dt = _split_proj(params, cfg, u)
    # conv: shift window
    win = jnp.concatenate([conv_state, xBC], axis=-2)      # (B, W, C)
    new_conv = win[..., 1:, :]
    out = jnp.einsum("bwc,wc->bc", win, params["conv_w"])
    xBC = jax.nn.silu(out + params["conv_b"])[:, None, :]
    x, Bm, Cm = jnp.split(xBC, [din, din + N], axis=-1)
    x = x.reshape(B, nh, hp)
    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32)
                         + params["dt_bias"])              # (B,nh)
    A = -jnp.exp(params["A_log"])
    dec = jnp.exp(dt * A[None, :])                         # (B,nh)
    Bv = Bm[:, 0].astype(jnp.float32)                      # (B,N)
    Cv = Cm[:, 0].astype(jnp.float32)
    contrib = jnp.einsum("bn,bh,bhp->bhpn", Bv, dt, x.astype(jnp.float32))
    ssm_state = ssm_state.astype(jnp.float32) * dec[..., None, None] + contrib
    y = jnp.einsum("bn,bhpn->bhp", Cv, ssm_state).astype(u.dtype)
    y = y + x * params["D"][None, :, None].astype(u.dtype)
    y = y.reshape(B, 1, din)
    y = L.rmsnorm(y * jax.nn.silu(z), params["norm"])
    return y @ params["out_proj"], new_conv, ssm_state.astype(u.dtype)


# ---------------------------------------------------------------------------
# model: train / prefill / decode
# ---------------------------------------------------------------------------

def block(cfg, layer, x, rules):
    h = L.rmsnorm(x, layer["ln"])
    y, _ = mixer_forward(layer["mixer"], cfg, h, rules)
    x = x + y
    return L.shard(x, P("DP", None, None), rules)


def loss_fn(cfg, params, batch, rules=None):
    x = L.embed(params["embed"], batch["tokens"]).astype(cfg.dtype())
    x = L.shard(x, P("DP", None, None), rules)

    def body(x, layer):
        return block(cfg, layer, x, rules), None
    if cfg.remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = L.rmsnorm(x, params["ln_f"])
    logits = L.unembed(params["embed"], x, rules)
    return L.softmax_xent(logits, batch["targets"], batch.get("mask"))


def init_cache(cfg, B, S, dtype=None):
    """Mamba cache is O(1) in context length: conv window + SSD state."""
    dt = dtype or cfg.dtype()
    din, N, nh = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    hp = din // nh
    conv_dim = din + 2 * N
    Lyr = cfg.n_layers
    return {"conv": jnp.zeros((Lyr, B, cfg.conv_width - 1, conv_dim), dt),
            "ssm": jnp.zeros((Lyr, B, nh, hp, N), dt)}


def cache_specs(cfg, rules=None):
    return {"conv": P(None, "DP", None, "TP"),
            "ssm": P(None, "DP", "TP", None, None)}


def prefill(cfg, params, batch, rules=None, cache_len=None):
    x = L.embed(params["embed"], batch["tokens"]).astype(cfg.dtype())
    x = L.shard(x, P("DP", None, None), rules)

    def body(x, layer):
        h = L.rmsnorm(x, layer["ln"])
        y, (conv_st, ssm_st) = mixer_forward(layer["mixer"], cfg, h, rules)
        x = L.shard(x + y, P("DP", None, None), rules)
        return x, (conv_st, ssm_st)

    if cfg.remat:
        body = jax.checkpoint(body)
    x, (convs, ssms) = jax.lax.scan(body, x, params["layers"])
    x = L.rmsnorm(x, params["ln_f"])
    logits = L.unembed(params["embed"], x[:, -1:], rules)
    return logits, {"conv": convs, "ssm": ssms}


def decode_step(cfg, params, cache, token, pos, rules=None):
    x = L.embed(params["embed"], token).astype(cfg.dtype())

    def body(x, inp):
        layer, conv_st, ssm_st = inp
        h = L.rmsnorm(x, layer["ln"])
        y, conv_st, ssm_st = mixer_decode(layer["mixer"], cfg, h,
                                          conv_st, ssm_st)
        return x + y, (conv_st, ssm_st)

    x, (convs, ssms) = jax.lax.scan(
        body, x, (params["layers"], cache["conv"], cache["ssm"]))
    x = L.rmsnorm(x, params["ln_f"])
    logits = L.unembed(params["embed"], x, rules)
    return logits, {"conv": convs, "ssm": ssms}
