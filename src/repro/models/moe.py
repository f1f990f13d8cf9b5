"""Mixture-of-Experts FFN with static-shape capacity-sort dispatch.

The paper's extreme "static weight" kernel class: expert weights are the
weight-stationary plane (ReRAM-macro analogue → expert-parallel sharding
over the ``model`` axis), while token dispatch is the dynamic many-to-few
traffic the NoI must carry (§3.2).

Dispatch is vmapped **per batch row** so the sort never crosses the
batch sharding axis: each row's S tokens are routed with an
argsort-by-expert + per-expert capacity, giving fully static shapes
(the GShard/Switch scheme without the O(T·E·C) one-hot blow-up).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.modules import activation, dense_init, init_mlp, apply_mlp
from repro.parallel import constrain


def init_moe(key, cfg, *, dtype=jnp.float32):
    D, E, Fe = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (D, E), jnp.float32),
        "experts": {
            "w_gate": dense_init(ks[1], (E, D, Fe), dtype),
            "w_up": dense_init(ks[2], (E, D, Fe), dtype),
            "w_down": dense_init(ks[3], (E, Fe, D), dtype, fan_in=Fe),
        },
    }
    if cfg.n_shared_experts:
        import dataclasses
        shared_cfg = dataclasses.replace(cfg, glu=True, mlp_bias=False)
        p["shared"] = init_mlp(ks[4], shared_cfg,
                               d_ff=cfg.n_shared_experts * Fe, dtype=dtype)
    return p


def _capacity(tokens: int, cfg) -> int:
    c = math.ceil(tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(min(int(c), tokens), 1)


def _dispatch_row(x, gates, idx, E: int, C: int, k: int):
    """x (S, D); gates/idx (S, k) -> (buf (E*C, D), slot (S*k,), tok (S*k,),
    keep (S*k,), gate_sorted (S*k,))."""
    S, D = x.shape
    flat_e = idx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    tok = order // k
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))
    rank = jnp.arange(S * k) - starts[sorted_e]
    keep = rank < C
    slot = jnp.where(keep, sorted_e * C + rank, E * C)
    buf = jnp.zeros((E * C, D), x.dtype).at[slot].set(x[tok], mode="drop")
    gate_sorted = gates.reshape(-1)[order]
    return buf, slot, tok, keep, gate_sorted


def apply_moe(p, x, cfg, *, mode: str = "train"):
    """x (B, S, D) -> (B, S, D).

    Two dispatch paths:
    - capacity-sort einsum (default): fully static shapes, expert axis
      shardable over ``model`` (EP) — the dry-run / training path.  Tokens
      beyond an expert's capacity are dropped (standard GShard semantics).
    - dropless grouped-matmul (``ragged_dot``): exact, no drops — used for
      single-host decode (serving engine, CPU tests) where static EP
      sharding isn't in play and decode-vs-prefill consistency matters.
    """
    from repro.parallel.api import current_plan

    B, S, D = x.shape
    E, k, Fe = cfg.n_experts, cfg.top_k, cfg.d_ff_expert
    C = _capacity(S, cfg)
    dt = x.dtype
    act = activation(cfg.act)

    logits = (x @ p["router"]).astype(jnp.float32)       # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                 # (B, S, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates = gates.astype(dt)

    if mode in ("prefill", "decode") and current_plan() is None:
        # single-host serving: exact dropless path, so decode continues
        # prefill bit-for-bit (capacity drops would make them diverge)
        y = _apply_dropless(p, x, gates, idx, cfg)
        if "shared" in p:
            y = y + apply_mlp(p["shared"], x, cfg)
        return y

    if current_plan() is not None and S > 1:
        # sharded execution: GShard one-hot einsum dispatch — einsums
        # partition cleanly under SPMD where the sort/scatter path
        # materialises unsharded (B, E·C, D) buffers (measured: 2.5 GiB +
        # 2 GiB per layer on qwen3-moe train_4k)
        y = _apply_gshard(p, x, gates, idx, cfg)
        if "shared" in p:
            y = y + apply_mlp(p["shared"], x, cfg)
        return y

    buf, slot, tok, keep, gate_sorted = jax.vmap(
        lambda xr, gr, ir: _dispatch_row(xr, gr, ir, E, C, k))(x, gates, idx)
    xe = buf.reshape(B, E, C, D)
    xe = constrain(xe, "expert_buf")

    we = p["experts"]
    h = act(jnp.einsum("becd,edf->becf", xe, we["w_gate"].astype(dt))) * \
        jnp.einsum("becd,edf->becf", xe, we["w_up"].astype(dt))
    h = constrain(h, "expert_hidden")
    ye = jnp.einsum("becf,efd->becd", h, we["w_down"].astype(dt))
    ye = constrain(ye, "expert_buf")
    yflat = ye.reshape(B, E * C, D)

    def _combine_row(yf, slot_r, tok_r, keep_r, gate_r):
        gathered = yf[jnp.minimum(slot_r, E * C - 1)] * keep_r[:, None]
        return jnp.zeros((S, D), yf.dtype).at[tok_r].add(gathered * gate_r[:, None])

    y = jax.vmap(_combine_row)(yflat, slot, tok, keep, gate_sorted)

    if "shared" in p:
        y = y + apply_mlp(p["shared"], x, cfg)
    return y


def _apply_gshard(p, x, gates, idx, cfg):
    """GShard-style dispatch: per-sequence-group one-hot dispatch/combine
    einsums with local capacity.  Groups are aligned to the sequence
    sharding (G = mesh model-axis size when it divides S), so the
    rank-cumsum is shard-local and every op partitions.

    x (B, S, D), gates/idx (B, S, k) -> (B, S, D)
    """
    from repro.parallel.api import current_plan

    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    dt = x.dtype
    act = activation(cfg.act)

    plan = current_plan()
    G = 1
    if plan is not None:
        g = plan.mesh.shape.get("model", 1)
        if S % g == 0:
            G = g
    Sg = S // G
    Cg = _capacity(Sg, cfg)

    xg = x.reshape(B, G, Sg, D)
    eg = idx.reshape(B, G, Sg, k)
    wg = gates.reshape(B, G, Sg, k)

    # position-in-expert ranks, k slots processed in priority order
    onehot = jax.nn.one_hot(eg, E, dtype=jnp.float32)     # (B,G,Sg,k,E)
    # tokens before s (all k slots) + earlier slots at s
    cum_tok = jnp.cumsum(onehot.sum(3), axis=2) - onehot.sum(3)  # (B,G,Sg,E)
    cum_slot = jnp.cumsum(onehot, axis=3) - onehot               # (B,G,Sg,k,E)
    rank = cum_tok[:, :, :, None, :] + cum_slot                  # (B,G,Sg,k,E)
    keep = (rank < Cg) & (onehot > 0)
    rank = jnp.sum(rank * onehot, axis=-1)                       # (B,G,Sg,k)
    keepk = jnp.any(keep, axis=-1)                               # (B,G,Sg,k)

    oh_c = jax.nn.one_hot(rank.astype(jnp.int32), Cg, dtype=jnp.float32)
    # dispatch (B,G,Sg,k,E,Cg) — contracted immediately, never fully live
    disp = (onehot[..., None] * oh_c[..., None, :]
            * keepk[..., None, None].astype(jnp.float32))
    disp_sum = disp.sum(3).astype(dt)                            # (B,G,Sg,E,Cg)
    comb = (disp * wg[..., None, None].astype(jnp.float32)
            ).sum(3).astype(dt)                                  # (B,G,Sg,E,Cg)

    xe = jnp.einsum("bgsec,bgsd->begcd", disp_sum, xg)           # (B,E,G,Cg,D)
    xe = xe.reshape(B, E, G * Cg, D)
    xe = constrain(xe, "expert_buf")

    we = p["experts"]
    h = act(jnp.einsum("becd,edf->becf", xe, we["w_gate"].astype(dt))) * \
        jnp.einsum("becd,edf->becf", xe, we["w_up"].astype(dt))
    h = constrain(h, "expert_hidden")
    ye = jnp.einsum("becf,efd->becd", h, we["w_down"].astype(dt))
    ye = constrain(ye, "expert_buf").reshape(B, E, G, Cg, D)

    y = jnp.einsum("bgsec,begcd->bgsd", comb, ye)
    return y.reshape(B, S, D)


def _apply_dropless(p, x, gates, idx, cfg):
    """Exact MoE via sorted grouped matmul (jax.lax.ragged_dot) — the
    MegaBlocks-style dropless path: every selected (token, expert) pair is
    computed, no capacity, shapes static in B·S·k."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    dt = x.dtype
    act = activation(cfg.act)
    we = p["experts"]

    xf = x.reshape(B * S, D)
    flat_e = idx.reshape(-1)                         # (B*S*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    tok = order // k                                 # source token per slot
    xs = xf[tok]                                     # (B*S*k, D) sorted by e
    group_sizes = jnp.bincount(sorted_e, length=E).astype(jnp.int32)

    h = act(jax.lax.ragged_dot(xs, we["w_gate"].astype(dt), group_sizes)) * \
        jax.lax.ragged_dot(xs, we["w_up"].astype(dt), group_sizes)
    ys = jax.lax.ragged_dot(h, we["w_down"].astype(dt), group_sizes)
    gate_sorted = gates.reshape(-1)[order]
    y = jnp.zeros((B * S, D), dt).at[tok].add(ys * gate_sorted[:, None])
    return y.reshape(B, S, D)


def router_aux_loss(p, x, cfg):
    """Switch-style load-balance loss (used by the training loop)."""
    logits = (x @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top1, cfg.n_experts, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    return cfg.n_experts * jnp.sum(frac_tokens * frac_probs)
