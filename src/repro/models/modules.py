"""Shared building blocks: norms, activations, RoPE, MLPs, init helpers."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.parallel import constrain
from repro.quant.ops import qdense

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[-2] if len(shape) >= 2 else shape[-1]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms (always computed in f32)
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)


def layernorm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def init_norm(key, cfg, width=None):
    d = width or cfg.d_model
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.zeros((d,), jnp.float32)}  # rmsnorm stores (scale-1)


def apply_norm(p, x):
    if "bias" in p:
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"])


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------

def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name!r}")


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_angles(positions, dim: int, theta: float):
    """positions (...,) int -> cos/sin of shape (..., dim//2), f32."""
    freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    cos, sin = rope_angles(positions, hd, theta)  # (B, S, hd/2)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(positions, dim: int):
    """Whisper/BERT-style absolute sinusoidal embedding, (..., dim) f32."""
    half = dim // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (math.log(10_000.0) / max(half - 1, 1)))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# MLP (dense FFN) — the paper's "static / ReRAM-macro" kernel class
# ---------------------------------------------------------------------------

def init_mlp(key, cfg, d_in=None, d_ff=None, *, dtype=jnp.float32):
    d = d_in or cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {}
    if cfg.glu:
        p["w_gate"] = dense_init(ks[0], (d, f), dtype)
        p["w_up"] = dense_init(ks[1], (d, f), dtype)
    else:
        p["w_up"] = dense_init(ks[1], (d, f), dtype)
    p["w_down"] = dense_init(ks[2], (f, d), dtype, fan_in=f)
    if cfg.mlp_bias:
        p["b_up"] = jnp.zeros((f,), dtype)
        p["b_down"] = jnp.zeros((d,), dtype)
    return p


def apply_mlp(p, x, cfg):
    act = activation(cfg.act)
    dt = x.dtype
    if cfg.glu:
        h = act(qdense(x, p["w_gate"], dt)) * qdense(x, p["w_up"], dt)
    else:
        h = qdense(x, p["w_up"], dt)
        if "b_up" in p:
            h = h + p["b_up"].astype(dt)
        h = act(h)
    h = constrain(h, "act_ff")
    y = qdense(h, p["w_down"], dt)
    if "b_down" in p:
        y = y + p["b_down"].astype(dt)
    return y
