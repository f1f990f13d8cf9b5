"""Model assembly: scan-grouped layer stacks, embeddings, loss / prefill /
decode drivers for every supported architecture family.

Depth is folded into ``jax.lax.scan`` groups (one scan per maximal run of
identical pattern periods) so HLO size and dry-run compile time are O(1)
in layer count — 100-layer configs compile as fast as 2-layer ones.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import modules as M
from repro.models.attention import (
    apply_attention, apply_mla, init_attention, init_mla, init_kv_cache,
    layer_of)
from repro.models.moe import apply_moe, init_moe, router_aux_loss
from repro.quant.ops import qdense
from repro.models.rglru import apply_rglru, init_rglru, init_rglru_cache
from repro.models.ssm import apply_mamba, init_mamba, init_ssm_cache
from repro.parallel import constrain


# ---------------------------------------------------------------------------
# group derivation
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GroupSpec:
    units: tuple[tuple[str, bool], ...]   # (layer_kind, use_moe)
    repeats: int


def build_groups(cfg: ModelConfig, *, encoder: bool = False) -> list[GroupSpec]:
    if encoder:
        kinds = cfg.encoder_layer_kinds
        moe = tuple(False for _ in kinds)
        period = len(cfg.encoder_pattern)
    else:
        kinds = cfg.layer_kinds
        moe = cfg.moe_layer_mask()
        period = len(cfg.pattern)
    units = tuple(zip(kinds, moe))
    n = len(units)
    groups: list[GroupSpec] = []
    full = n // period
    periods = [units[i * period:(i + 1) * period] for i in range(full)]
    i = 0
    while i < len(periods):
        j = i
        while j < len(periods) and periods[j] == periods[i]:
            j += 1
        groups.append(GroupSpec(periods[i], j - i))
        i = j
    rem = units[full * period:]
    if rem:
        groups.append(GroupSpec(rem, 1))
    return groups


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg, kind: str, use_moe: bool, *, causal: bool, dtype):
    ks = jax.random.split(key, 8)
    p: dict[str, Any] = {}
    if kind == "ssm":
        p["ln1"] = M.init_norm(ks[0], cfg)
        p["mamba"] = init_mamba(ks[1], cfg, dtype=dtype)
        return p
    if kind == "recurrent":
        p["ln1"] = M.init_norm(ks[0], cfg)
        p["rec"] = init_rglru(ks[1], cfg, dtype=dtype)
        p["ln2"] = M.init_norm(ks[2], cfg)
        p["mlp"] = M.init_mlp(ks[3], cfg, dtype=dtype)
        return p
    if kind == "cross":  # vlm gated cross-attention layer
        p["ln1"] = M.init_norm(ks[0], cfg)
        p["attn"] = init_attention(ks[1], cfg, cross=True, dtype=dtype)
        p["gate_attn"] = jnp.zeros((), jnp.float32)
        p["ln2"] = M.init_norm(ks[2], cfg)
        p["mlp"] = M.init_mlp(ks[3], cfg, dtype=dtype)
        p["gate_mlp"] = jnp.zeros((), jnp.float32)
        return p
    # global / local attention layer
    p["ln1"] = M.init_norm(ks[0], cfg)
    if cfg.is_mla:
        p["attn"] = init_mla(ks[1], cfg, dtype=dtype)
    else:
        p["attn"] = init_attention(ks[1], cfg, dtype=dtype)
    if cfg.parallel_block:
        p["mlp"] = M.init_mlp(ks[3], cfg, dtype=dtype)
        return p
    if cfg.post_norm:
        p["ln1_post"] = M.init_norm(ks[4], cfg)
    if cfg.cross_attn_decoder and causal:
        p["ln_cross"] = M.init_norm(ks[5], cfg)
        p["cross"] = init_attention(ks[6], cfg, cross=True, dtype=dtype)
    p["ln2"] = M.init_norm(ks[2], cfg)
    if use_moe:
        p["moe"] = init_moe(ks[3], cfg, dtype=dtype)
    else:
        p["mlp"] = M.init_mlp(ks[3], cfg, dtype=dtype)
    if cfg.post_norm:
        p["ln2_post"] = M.init_norm(ks[7], cfg)
    return p


def _init_block(key, cfg, spec: GroupSpec, *, causal: bool, dtype):
    ks = jax.random.split(key, len(spec.units))
    return {f"u{i}": _init_layer(ks[i], cfg, kind, use_moe, causal=causal, dtype=dtype)
            for i, (kind, use_moe) in enumerate(spec.units)}


def _init_stack(key, cfg, groups, *, causal: bool, dtype):
    gparams = []
    for gi, spec in enumerate(groups):
        gkey = jax.random.fold_in(key, gi)
        keys = jax.random.split(gkey, spec.repeats)
        blk = jax.vmap(lambda k: _init_block(k, cfg, spec, causal=causal, dtype=dtype))(keys)
        gparams.append(blk)
    return gparams


def init_params(cfg: ModelConfig, key, *, param_dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    params: dict[str, Any] = {
        "embed": {"tok": M.embed_init(ks[0], (cfg.vocab_size, cfg.d_model), param_dtype)},
    }
    if not cfg.use_rope and cfg.family not in ("audio",) and cfg.max_abs_positions:
        params["embed"]["pos"] = M.embed_init(
            ks[1], (cfg.max_abs_positions, cfg.d_model), param_dtype)
    causal = cfg.family != "encoder"
    params["stack"] = _init_stack(ks[2], cfg, build_groups(cfg), causal=causal,
                                  dtype=param_dtype)
    params["final_norm"] = M.init_norm(ks[3], cfg)
    if cfg.n_encoder_layers:
        params["encoder"] = _init_stack(ks[4], cfg, build_groups(cfg, encoder=True),
                                        causal=False, dtype=param_dtype)
        params["encoder_norm"] = M.init_norm(ks[5], cfg)
    if not cfg.tie_embeddings:
        params["lm_head"] = M.dense_init(
            jax.random.fold_in(key, 99), (cfg.d_model, cfg.vocab_size), param_dtype)
    return params


# ---------------------------------------------------------------------------
# per-layer apply
# ---------------------------------------------------------------------------

def _apply_layer(p, x, *, cfg, kind, use_moe, mode, pos, cache, cross_src,
                 impl, causal, kv_cap=0, length=None, segments=None,
                 kv_bits=0, layer=None):
    """``layer``: ``cache["attn"]`` is the layer-stacked self-attention pool
    and this layer's rows commit into it in place (decode / chunk)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = None
    if kind == "ssm":
        h = M.apply_norm(p["ln1"], x)
        out, new_cache = apply_mamba(p["mamba"], h, cfg=cfg, mode=mode,
                                     cache=cache, length=length)
        x = constrain(x + out, "residual")
        return x, new_cache, aux
    if kind == "recurrent":
        h = M.apply_norm(p["ln1"], x)
        out, c = apply_rglru(p["rec"], h, cfg=cfg, mode=mode, cache=cache,
                             length=length)
        x = constrain(x + out, "residual")
        h = M.apply_norm(p["ln2"], x)
        x = constrain(x + M.apply_mlp(p["mlp"], h, cfg), "residual")
        return x, c, aux
    if kind == "cross":
        h = M.apply_norm(p["ln1"], x)
        out, c = apply_attention(p["attn"], h, cfg=cfg, kind="cross", mode=mode,
                                 pos=pos, cache=cache, cross_src=cross_src,
                                 impl=impl, causal=False)
        x = constrain(x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * out, "residual")
        h = M.apply_norm(p["ln2"], x)
        x = x + jnp.tanh(p["gate_mlp"]).astype(x.dtype) * M.apply_mlp(p["mlp"], h, cfg)
        return constrain(x, "residual"), c, aux

    # global / local attention layer
    h = M.apply_norm(p["ln1"], x)
    if cfg.parallel_block:  # GPT-J eq. (9): parallel MHA + FF
        c_self = cache["attn"] if cache is not None else None
        out, c = apply_attention(p["attn"], h, cfg=cfg, kind=kind, mode=mode,
                                 pos=pos, cache=c_self, impl=impl, causal=causal,
                                 kv_cap=kv_cap, length=length, segments=segments,
                                 kv_bits=kv_bits, layer=layer)
        x = constrain(x + out + M.apply_mlp(p["mlp"], h, cfg), "residual")
        return x, ({"attn": c} if mode != "train" else None), aux

    if cfg.is_mla:
        c_self = cache["attn"] if cache is not None else None
        out, c = apply_mla(p["attn"], h, cfg=cfg, mode=mode, pos=pos,
                           cache=c_self, impl=impl, kv_cap=kv_cap,
                           length=length, segments=segments)
    else:
        c_self = cache["attn"] if cache is not None else None
        out, c = apply_attention(p["attn"], h, cfg=cfg, kind=kind, mode=mode,
                                 pos=pos, cache=c_self, impl=impl, causal=causal,
                                 kv_cap=kv_cap, length=length, segments=segments,
                                 kv_bits=kv_bits, layer=layer)
    if cfg.post_norm:
        out = M.apply_norm(p["ln1_post"], out)
    x = constrain(x + out, "residual")

    c_cross = None
    if "cross" in p:
        h = M.apply_norm(p["ln_cross"], x)
        c_cross_in = cache["cross"] if cache is not None else None
        out, c_cross = apply_attention(p["cross"], h, cfg=cfg, kind="cross",
                                       mode=mode, pos=pos, cache=c_cross_in,
                                       cross_src=cross_src, impl=impl, causal=False)
        x = constrain(x + out, "residual")

    h = M.apply_norm(p["ln2"], x)
    if use_moe:
        ff = apply_moe(p["moe"], h, cfg, mode=mode)
        if mode == "train":
            aux = router_aux_loss(p["moe"], h, cfg)
    else:
        ff = M.apply_mlp(p["mlp"], h, cfg)
    if cfg.post_norm:
        ff = M.apply_norm(p["ln2_post"], ff)
    x = constrain(x + ff, "residual")

    if mode == "train":
        blk_cache = None
    else:
        blk_cache = {"attn": c}
        if "cross" in p:
            blk_cache["cross"] = c_cross
    return x, blk_cache, aux


# ---------------------------------------------------------------------------
# stack runner (scan groups)
# ---------------------------------------------------------------------------

# Decode / chunk groups with at most this many repeats run the layer body
# as an unrolled Python loop; larger groups scan it.  The small
# configurations the CPU tests run stay unrolled: XLA's CPU backend rounds
# bf16 differently inside a loop body, and their greedy token streams,
# compared across serving paths and with recorded ones, hold near ties
# that this rounding decides.
DECODE_UNROLL = 8


def _commits_in_place(cfg, kind: str) -> bool:
    """Whether a unit of this kind commits its decode / chunk K/V rows in
    place into the layer-stacked pool (standard and parallel-block self
    attention, fp or quantised); every other unit kind returns its whole
    layer state, which the layer loop writes back."""
    return kind in ("global", "local") and not cfg.is_mla


def kv_commit_layers(cfg, groups) -> tuple[int, int]:
    """Per decode / chunk step: (layers that commit K/V rows in place,
    layers that write back a whole layer state).  A self-attention layer
    that also holds a cross-attention cache counts in both."""
    inplace = writeback = 0
    for spec in groups:
        for kind, _ in spec.units:
            own = _commits_in_place(cfg, kind)
            inplace += spec.repeats * own
            writeback += spec.repeats * (not own or cfg.cross_attn_decoder)
    return inplace, writeback


def _write_layer(pool, one, r):
    return jax.tree_util.tree_map(
        lambda a, o: jax.lax.dynamic_update_index_in_dim(
            a, o.astype(a.dtype), r, 0), pool, one)


def _apply_block(p_blk, x, cache_blk, *, cfg, spec, mode, pos, cross_src,
                 impl, causal, kv_cap=0, length=None, segments=None,
                 kv_bits=0, layer=None):
    """``layer`` (layer index): ``cache_blk`` is the group's stacked
    pool and the returned cache is that pool with this layer's state
    committed (see :func:`run_stack`)."""
    new_cache = {}
    aux_total = jnp.zeros((), jnp.float32)
    for ui, (kind, use_moe) in enumerate(spec.units):
        c_in = None if cache_blk is None else cache_blk.get(f"u{ui}")
        # sub-caches that stay stacked and take their rows in place; every
        # other one is sliced to this layer and written back whole
        own = ("attn",) if layer is not None and _commits_in_place(cfg, kind) \
            else ()
        if layer is not None:
            c_in = {n: c if n in own else layer_of(c, layer)
                    for n, c in c_in.items()}
        x, c_out, aux = _apply_layer(
            p_blk[f"u{ui}"], x, cfg=cfg, kind=kind, use_moe=use_moe, mode=mode,
            pos=pos, cache=c_in, cross_src=cross_src, impl=impl, causal=causal,
            kv_cap=kv_cap, length=length, segments=segments, kv_bits=kv_bits,
            layer=layer if own else None)
        if layer is not None:
            pool = cache_blk[f"u{ui}"]
            c_out = {n: c_out[n] if n in own
                     else _write_layer(pool[n], c_out[n], layer) for n in pool}
        new_cache[f"u{ui}"] = c_out
        aux_total = aux_total + aux
    return x, (new_cache if mode != "train" else None), aux_total


def run_stack(stack_params, x, *, cfg, groups, mode, pos, caches=None,
              cross_src=None, impl="auto", causal=True, remat=False,
              remat_policy: Optional[str] = None, kv_cap=0,
              length=None, segments=None, kv_bits=0):
    """Run the layer groups, each as one ``lax.scan`` over its repeats.

    Decode and chunk modes with a cache scan over (group params, layer
    index) and carry ``(x, pool)``: each self-attention layer scatters its
    new K/V/pos rows into the stacked pool at ``[layer, row, slot]`` and
    reads its own layer from it, so a donated pool is updated in place —
    no per-layer write-back and no stacked copy of the pool after the
    loop.  Other unit kinds (MLA, cross, SSM, recurrent) return their layer
    state, written back into the carried pool with one
    ``dynamic_update_index_in_dim`` (:func:`kv_commit_layers` counts
    both).  Groups of at most :data:`DECODE_UNROLL` repeats run the same
    layer body unrolled.  Train and prefill scan over the params alone,
    prefill stacking the per-layer caches it builds as scan outputs."""
    new_caches = []
    aux_total = jnp.zeros((), jnp.float32)
    for gi, spec in enumerate(groups):
        gp = stack_params[gi]
        gc = None if caches is None else caches[gi]

        if mode in ("decode", "chunk") and gc is not None:
            def layer_step(carry, xs, spec=spec):
                x, pool = carry
                p_blk, r = xs
                x, pool, _ = _apply_block(
                    p_blk, x, pool, cfg=cfg, spec=spec, mode=mode, pos=pos,
                    cross_src=cross_src, impl=impl, causal=causal,
                    kv_cap=kv_cap, length=length, segments=segments,
                    kv_bits=kv_bits, layer=r)
                return (x, pool), None

            carry = (x, gc)
            if spec.repeats <= DECODE_UNROLL:
                for r in range(spec.repeats):
                    p_blk = jax.tree_util.tree_map(lambda p, r=r: p[r], gp)
                    carry, _ = layer_step(carry, (p_blk, r))
            else:
                carry, _ = jax.lax.scan(
                    layer_step, carry,
                    (gp, jnp.arange(spec.repeats, dtype=jnp.int32)))
            x, gc = carry
            new_caches.append(gc)
            continue

        def step(x, p_blk, spec=spec):
            x, c_out, aux = _apply_block(
                p_blk, x, None, cfg=cfg, spec=spec, mode=mode, pos=pos,
                cross_src=cross_src, impl=impl, causal=causal, kv_cap=kv_cap,
                length=length, segments=segments, kv_bits=kv_bits)
            return x, (c_out, aux)

        if remat:
            policy = None
            if remat_policy == "dots":
                policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            step = jax.checkpoint(step, policy=policy)

        x, (c_stacked, aux) = jax.lax.scan(step, x, gp)
        new_caches.append(c_stacked)
        aux_total = aux_total + jnp.sum(aux)
    return x, new_caches, aux_total


# ---------------------------------------------------------------------------
# embeddings / logits
# ---------------------------------------------------------------------------

def embed_tokens(params, cfg, tokens, pos, dtype):
    h = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(dtype)
    if cfg.embed_scale:
        h = h * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    if not cfg.use_rope:
        if "pos" in params["embed"]:
            pe = jnp.take(params["embed"]["pos"], pos, axis=0).astype(dtype)
        else:  # sinusoidal stub (whisper)
            pe = M.sinusoidal_positions(pos, cfg.d_model).astype(dtype)
        h = h + pe
    return constrain(h, "residual")


def unembed(params, cfg, h):
    h = constrain(h, "pre_logits")
    if cfg.tie_embeddings:
        w = params["embed"]["tok"]
        logits = jnp.einsum("bsd,vd->bsv", h, w.astype(h.dtype))
    else:
        logits = qdense(h, params["lm_head"], h.dtype)
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(
            logits.astype(jnp.float32) / cfg.final_softcap).astype(logits.dtype)
    return constrain(logits, "logits")


def _run_encoder(params, cfg, batch, dtype, impl, remat=False,
                 remat_policy=None):
    if cfg.family == "audio":
        h = batch["frames"].astype(dtype)  # precomputed frame embeddings (stub)
        S = h.shape[1]
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), h.shape[:2])
        h = h + M.sinusoidal_positions(pos, cfg.d_model).astype(dtype)
        h = constrain(h, "residual")
    else:  # bart-style text encoder
        toks = batch["encoder_tokens"]
        pos = jnp.broadcast_to(jnp.arange(toks.shape[1], dtype=jnp.int32), toks.shape)
        h = embed_tokens(params, cfg, toks, pos, dtype)
    groups = build_groups(cfg, encoder=True)
    h, _, _ = run_stack(params["encoder"], h, cfg=cfg, groups=groups,
                        mode="train", pos=pos, impl=impl, causal=False,
                        remat=remat, remat_policy=remat_policy)
    return M.apply_norm(params["encoder_norm"], h)


def _cross_source(params, cfg, batch, dtype, impl, remat=False,
                  remat_policy=None):
    if cfg.n_encoder_layers:
        return _run_encoder(params, cfg, batch, dtype, impl, remat,
                            remat_policy)
    if cfg.family == "vlm":
        return batch["image_embeds"].astype(dtype)  # patch embeddings (stub)
    return None


# ---------------------------------------------------------------------------
# public drivers
# ---------------------------------------------------------------------------

def loss_fn(params, cfg: ModelConfig, batch, *, impl="auto",
            compute_dtype=jnp.bfloat16, remat=False, remat_policy=None,
            aux_weight=0.01):
    """batch: tokens (B,S) [+ frames | encoder_tokens | image_embeds]."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cross_src = _cross_source(params, cfg, batch, compute_dtype, impl,
                              remat, remat_policy)
    causal = cfg.family != "encoder"

    h = embed_tokens(params, cfg, tokens, pos, compute_dtype)
    h, _, aux = run_stack(params["stack"], h, cfg=cfg, groups=build_groups(cfg),
                          mode="train", pos=pos, cross_src=cross_src, impl=impl,
                          causal=causal, remat=remat, remat_policy=remat_policy)
    h = M.apply_norm(params["final_norm"], h)
    logits = unembed(params, cfg, h)

    lf = logits.astype(jnp.float32)
    if causal:
        lf = lf[:, :-1]
        targets = tokens[:, 1:]
    else:  # encoder (BERT-class): MLM-style proxy on fixed positions
        keep = (jnp.arange(S) % 7) == 3
        lf = lf
        targets = tokens
    logz = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if not causal:
        nll = jnp.where(keep[None, :], nll, 0.0)
        loss = nll.sum() / (keep.sum() * B)
    else:
        loss = nll.mean()
    return loss + aux_weight * aux, {"nll": loss, "aux": aux}


def prefill(params, cfg: ModelConfig, batch, *, impl="auto",
            compute_dtype=jnp.bfloat16, kv_cap: int = 0, length=None,
            kv_bits: int = 0):
    """Returns (last-token logits (B, V), cache).

    ``length`` (optional traced scalar): true prompt length when ``tokens``
    is right-padded to a static shape — logits are taken at position
    ``length - 1`` instead of the last position.  Causal masking makes
    attention exact under padding; ``length`` is also threaded into the
    stateful layer kinds (ring-buffer local attention, SSM, RG-LRU) so the
    *cache* at ``length`` is exact too — any prompt length can be served
    from a handful of padded compile shapes.
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cross_src = _cross_source(params, cfg, batch, compute_dtype, impl)

    h = embed_tokens(params, cfg, tokens, pos, compute_dtype)
    h, caches, _ = run_stack(params["stack"], h, cfg=cfg, groups=build_groups(cfg),
                             mode="prefill", pos=pos, cross_src=cross_src,
                             impl=impl, causal=True, kv_cap=kv_cap,
                             length=length, kv_bits=kv_bits)
    h = M.apply_norm(params["final_norm"], h)
    if length is None:
        last = h[:, -1:]
    else:
        last = jax.lax.dynamic_slice_in_dim(h, length - 1, 1, axis=1)
    logits = unembed(params, cfg, last)[:, 0]
    return logits, {"stack": caches}


def prefill_packed(params, cfg: ModelConfig, tokens, positions, segments,
                   gather_idx, *, impl="auto", compute_dtype=jnp.bfloat16,
                   kv_bits: int = 0):
    """Packed ragged prefill: several prompts in one ``(1, C)`` stream.

    ``positions`` are within-prompt positions (used for RoPE / absolute
    embeddings), ``segments`` per-token prompt ids (-1 = pad) — a query
    never attends across a prompt boundary.  ``gather_idx`` (n_seg,) picks
    the packed index of each prompt's last token; returns
    (logits (n_seg, V), raw per-token cache) — cache k/v/pos leaves keep
    the packed stream layout, the caller scatters segments into KV slots.

    Only attention layer kinds can be packed (SSM / recurrent state would
    integrate across prompt boundaries).
    """
    if not all(k in ("global", "local") for k in cfg.layer_kinds):
        raise ValueError(
            f"packed prefill needs attention-only stacks, got {cfg.layer_kinds}")
    h = embed_tokens(params, cfg, tokens, jnp.maximum(positions, 0),
                     compute_dtype)
    h, caches, _ = run_stack(params["stack"], h, cfg=cfg,
                             groups=build_groups(cfg), mode="prefill",
                             pos=positions, impl=impl, causal=True,
                             segments=segments, kv_bits=kv_bits)
    h = M.apply_norm(params["final_norm"], h)
    last = h[0][gather_idx][:, None]                    # (n_seg, 1, D)
    logits = unembed(params, cfg, last)[:, 0]
    return logits, {"stack": caches}


def chunk_prefill_step(params, cfg: ModelConfig, cache, tokens, pos, take_idx,
                       *, impl="auto", compute_dtype=jnp.bfloat16):
    """One chunked-prefill continuation step over the slot pool.

    ``tokens`` (B, C): next chunk per row (right-padded); ``pos`` (B, C):
    absolute positions, -1 = pad / inactive row; ``take_idx`` (B,): index
    of each row's last real chunk token (0 for inactive rows).  Chunk K/V
    is written into each row's cache at its positions, and the chunk
    attends to the whole cache — later chunks of a long prompt see the KV
    of earlier chunks.  Returns (logits (B, V) at take_idx, cache).
    """
    h = embed_tokens(params, cfg, tokens, jnp.maximum(pos, 0), compute_dtype)
    h, caches, _ = run_stack(params["stack"], h, cfg=cfg,
                             groups=build_groups(cfg), mode="chunk", pos=pos,
                             caches=cache["stack"], impl=impl, causal=True)
    h = M.apply_norm(params["final_norm"], h)
    last = jnp.take_along_axis(h, take_idx[:, None, None], axis=1)  # (B,1,D)
    logits = unembed(params, cfg, last)[:, 0]
    return logits, {"stack": caches}


def verify_step(params, cfg: ModelConfig, cache, tokens, pos, *, impl="auto",
                compute_dtype=jnp.bfloat16):
    """Batched multi-position scoring step (speculative-decoding verify).

    Identical mechanics to :func:`chunk_prefill_step` — ``tokens`` (B, C)
    are written into each row's cache at explicit absolute positions
    ``pos`` (B, C) (-1 = pad / inactive row) and attend to the pre-write
    cache plus the in-stream block — but the logits are kept at **every**
    chunk position instead of one ``take_idx`` gather: one call scores all
    k draft tokens of a speculative step (logits at in-stream index ``i``
    are the target's distribution for the token *after* ``tokens[:, i]``).
    Returns (logits (B, C, V), cache).
    """
    h = embed_tokens(params, cfg, tokens, jnp.maximum(pos, 0), compute_dtype)
    h, caches, _ = run_stack(params["stack"], h, cfg=cfg,
                             groups=build_groups(cfg), mode="chunk", pos=pos,
                             caches=cache["stack"], impl=impl, causal=True)
    h = M.apply_norm(params["final_norm"], h)
    logits = unembed(params, cfg, h)                        # (B, C, V)
    return logits, {"stack": caches}


def decode_step(params, cfg: ModelConfig, cache, tokens, pos, *, impl="auto",
                compute_dtype=jnp.bfloat16):
    """One decode step.  tokens (B,), pos (B,) -> (logits (B, V), cache)."""
    B = tokens.shape[0]
    pos2 = pos[:, None]
    h = embed_tokens(params, cfg, tokens[:, None], pos2, compute_dtype)
    h, caches, _ = run_stack(params["stack"], h, cfg=cfg, groups=build_groups(cfg),
                             mode="decode", pos=pos2, caches=cache["stack"],
                             impl=impl, causal=True)
    h = M.apply_norm(params["final_norm"], h)
    logits = unembed(params, cfg, h)[:, 0]
    return logits, {"stack": caches}


# ---------------------------------------------------------------------------
# cache init (dry-run decode inputs + serving engine)
# ---------------------------------------------------------------------------

def _init_layer_cache(cfg, kind, batch, kv_len, dtype, kv_bits=0):
    if kind == "ssm":
        return init_ssm_cache(cfg, batch, dtype)
    if kind == "recurrent":
        return init_rglru_cache(cfg, batch, dtype)
    n_cross = cfg.n_frontend_tokens
    if kind == "cross":
        return init_kv_cache(cfg, "cross", batch, kv_len, dtype, n_cross=n_cross)
    c = {"attn": init_kv_cache(cfg, kind, batch, kv_len, dtype,
                               kv_bits=kv_bits)}
    if cfg.cross_attn_decoder:
        c["cross"] = init_kv_cache(cfg, "cross", batch, kv_len, dtype, n_cross=n_cross)
        return c
    return c


def init_cache(cfg: ModelConfig, batch: int, kv_len: int, *,
               dtype=jnp.bfloat16, kv_bits: int = 0):
    groups = build_groups(cfg)
    caches = []
    for spec in groups:
        def one(kind=None):
            return {f"u{ui}": _init_layer_cache(cfg, kd, batch, kv_len, dtype,
                                                kv_bits=kv_bits)
                    for ui, (kd, _) in enumerate(spec.units)}
        blk = one()
        stacked = jax.tree_util.tree_map(
            lambda leaf: jnp.broadcast_to(leaf, (spec.repeats,) + leaf.shape).copy()
            if spec.repeats > 1 else leaf[None], blk)
        caches.append(stacked)
    return {"stack": caches}


# ---------------------------------------------------------------------------
# parameter counting
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _param_shapes(cfg: ModelConfig):
    shapes = jax.eval_shape(
        lambda k: init_params(cfg, k), jax.ShapeDtypeStruct((2,), jnp.uint32))
    return jax.tree_util.tree_flatten_with_path(shapes)[0]


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    total = 0.0
    for path, leaf in _param_shapes(cfg):
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        n = 1
        for s in leaf.shape:
            n *= s
        if active_only:
            if any(k in ("tok", "pos") for k in keys) and not (
                    cfg.tie_embeddings and "tok" in keys):
                continue  # untied embedding tables don't do matmul FLOPs
            if "experts" in keys:
                n = n * cfg.top_k / cfg.n_experts
        total += n
    return int(total)
