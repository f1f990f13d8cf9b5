"""Executor layer: the jitted device programs of the serving engine and
its single device→host transfer point.

Everything that touches XLA lives here — the fused decode step
(decode → sample → bookkeeping with a donated cache), the packed ragged
prefill, the chunked-prefill continuation, the per-request prefill+insert
of the sequential baseline, and the ``fused=False`` host-looped pieces.
The executor owns the (optionally quantised) parameters, the mesh plans
(``parallel.sharding.serving_decode_plan`` / ``serving_prefill_plan``)
and the host-transfer accounting; it holds **no** request or slot
bookkeeping — callers pass ``(cache, state)`` in and adopt what comes
back, so scheduling policy (``scheduler.py``) and slot lifecycle
(``pool.py``) are independently testable.

The function bodies are the pre-layering engine's jitted cores, moved
verbatim: under the default config every compiled program, donation
alias and sampled token is bit-identical to the monolith (pinned by
``tests/test_serving.py`` against recorded token streams).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.models import transformer as T
from repro.parallel.api import activate_plan


class Executor:
    def __init__(self, cfg: ModelConfig, params, ecfg, *, mesh=None):
        self.cfg, self.ecfg = cfg, ecfg
        self.params = params
        if ecfg.weight_bits:
            from repro.quant.core import quantize_params
            self.params = quantize_params(params, ecfg.weight_bits,
                                          group=ecfg.weight_group)

        # optional decode/prefill sharding plans for the slot pool
        self._plan = None
        self._prefill_plan = None
        self.shard_ctx = None          # consumed by SlotPool for the cache
        if mesh is not None:
            from repro.parallel.sharding import (serving_decode_plan,
                                                 serving_prefill_plan)
            self._plan, self.shard_ctx = serving_decode_plan(
                cfg, mesh, max_batch=ecfg.max_batch, kv_len=ecfg.kv_len)
            self._prefill_plan, _ = serving_prefill_plan(
                cfg, mesh, prefill_chunk=min(ecfg.prefill_chunk
                                             or min(128, ecfg.kv_len),
                                             ecfg.kv_len))

        # layers per decode / chunk step that commit K/V rows in place, and
        # that write back a whole layer state (models.transformer.run_stack)
        self.kv_rows_inplace_layers, self.kv_writeback_layers = \
            T.kv_commit_layers(cfg, T.build_groups(cfg))

        # host-transfer accounting (benchmarks/perf_serving.py)
        self.host_transfers = 0
        self.host_bytes = 0

        # -- speculative decoding (dormant unless ecfg.spec_k > 0) ----------
        # self-speculation drafts with a quantised copy of the serving
        # params (spec_draft_bits; 0 = the serving params themselves);
        # draft-model speculation gets its (cfg, params) via set_draft()
        self.draft_cfg = cfg
        self.draft_params = None
        if getattr(ecfg, "spec_k", 0) > 0:
            if ecfg.spec_draft == "self":
                if ecfg.spec_draft_bits:
                    from repro.quant.core import quantize_params
                    self.draft_params = quantize_params(
                        params, ecfg.spec_draft_bits, group=ecfg.weight_group)
                else:
                    self.draft_params = self.params
            self.jit_spec_step = jax.jit(self._spec_step_fn,
                                         donate_argnums=(2, 3, 4))
            self.jit_draft_prefill = jax.jit(self._draft_prefill_fn,
                                             donate_argnums=(1,))

        # -- fused path ------------------------------------------------------
        self.jit_step = jax.jit(self._fused_step_fn, donate_argnums=(1, 2))
        self.jit_prefill_insert = jax.jit(self._prefill_insert_fn,
                                          donate_argnums=(1, 2))
        self.jit_packed_prefill = jax.jit(self._packed_prefill_fn,
                                          donate_argnums=(1, 2))
        self.jit_chunk_step = jax.jit(self._chunk_step_fn,
                                      donate_argnums=(1, 2))
        # -- seed-compat path (fused=False) ----------------------------------
        self.jit_decode = jax.jit(self._decode_fn)
        self.jit_prefill = jax.jit(self._prefill_fn)
        self.jit_insert = jax.jit(self._insert_fn, donate_argnums=(0,))

    # -- device→host choke point ---------------------------------------------
    def fetch(self, x) -> np.ndarray:
        """The engine's single device→host transfer point (explicit, so
        tests can fence everything else with a d2h transfer guard)."""
        arr = jax.device_get(x)
        arr = np.asarray(arr)
        self.host_transfers += 1
        self.host_bytes += arr.nbytes
        return arr

    # -- public wrappers (what the engine drives) ------------------------------
    def fused_step(self, cache, state):
        return self.jit_step(self.params, cache, state)

    def prefill_insert(self, cache, state, tokens, slot, length, budget):
        return self.jit_prefill_insert(self.params, cache, state, tokens,
                                       slot, length, budget)

    def packed_prefill(self, cache, state, *args):
        return self.jit_packed_prefill(self.params, cache, state, *args)

    def chunk_step(self, cache, state, *args):
        return self.jit_chunk_step(self.params, cache, state, *args)

    def spec_step(self, cache, state, dcache=None):
        """One speculative decode step: draft ``spec_k`` tokens, verify
        them in a single batched multi-position call, commit the accepted
        prefix and roll back the rest.  ``dcache`` is the draft-model KV
        pool (None for self-speculation, which shares ``cache``)."""
        return self.jit_spec_step(self.params, self.draft_params, cache,
                                  dcache, state)

    def set_draft(self, draft_cfg, draft_params):
        """Attach a separate draft model (spec_draft='model')."""
        self.draft_cfg = draft_cfg
        self.draft_params = draft_params

    def draft_prefill(self, dcache, tokens, slot, length):
        """Mirror a completed prompt into the draft-model cache (one
        padded batch-1 draft prefill + slot insert)."""
        return self.jit_draft_prefill(self.draft_params, dcache, tokens,
                                      slot, length)

    def decode(self, cache, tokens, pos):
        return self.jit_decode(self.params, cache, tokens, pos)

    def prefill(self, tokens, length):
        return self.jit_prefill(self.params, tokens, length)

    def insert(self, cache, pcache, slot, length):
        return self.jit_insert(cache, pcache, slot, length)

    def sample_host(self, logits, key):
        """Host-path sampling (fused=False baseline): returns the sampled
        token array (fetched) and the advanced PRNG key."""
        if self.ecfg.temperature <= 0.0:
            return self.fetch(jnp.argmax(logits, axis=-1)), key
        key, sub = jax.random.split(key)
        return self.fetch(jax.random.categorical(
            sub, logits / self.ecfg.temperature, axis=-1)), key

    # -- jitted cores: fused path ---------------------------------------------
    def _sample_dev(self, logits, key):
        if self.ecfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), key
        key, sub = jax.random.split(key)
        nxt = jax.random.categorical(sub, logits / self.ecfg.temperature,
                                     axis=-1)
        return nxt.astype(jnp.int32), key

    def _fused_step_fn(self, params, cache, state):
        """decode → sample → bookkeeping, all on device.  Runs
        ``decode_chunk`` iterations (lax.scan for >1) and returns the new
        (cache, state) plus a packed (K, 3, B) int32 of (next_token | -1,
        done, anomaly) — the only array the host reads back per step.

        A slot whose logits come back non-finite is *frozen*: no token
        committed, pos/budget untouched, still live — the identical step
        re-runs next iteration (the KV write at the same pos is
        idempotent), so a transient fault costs one retry and a persistent
        one is quarantined by the host without touching the other slots
        (decode is batch-parallel, no cross-slot mixing).  With finite
        logits ``ok == live`` and every value below reduces to the
        anomaly-free step bit-identically."""
        def one(carry, _):
            cache, state = carry
            live = state["live"]
            # dead / mid-prefill slots write at pos -1 → dropped, so a
            # half-prefilled row is never corrupted by the decode sweep
            pos_w = jnp.where(live, state["pos"], -1)
            logits, cache = T.decode_step(params, self.cfg, cache,
                                          state["tokens"], pos_w,
                                          impl=self.ecfg.impl)
            nxt, key = self._sample_dev(logits, state["key"])
            bad = ~jnp.all(jnp.isfinite(logits), axis=-1)
            ok = live & ~bad
            pos_new = jnp.where(ok, state["pos"] + 1, state["pos"])
            budget_new = jnp.where(ok, state["budget"] - 1, state["budget"])
            done = (budget_new <= 0) | (pos_new >= self.ecfg.kv_len)
            if self.ecfg.eos_token >= 0:
                done = done | (nxt == self.ecfg.eos_token)
            done = ok & done
            packed = jnp.stack([jnp.where(ok, nxt, -1),
                                done.astype(jnp.int32),
                                (live & bad).astype(jnp.int32)])
            state = {
                "tokens": jnp.where(ok, nxt, state["tokens"]),
                "pos": pos_new,
                "budget": budget_new,
                "live": live & ~done,
                "key": key,
            }
            return (cache, state), packed

        with activate_plan(self._plan):
            chunk = max(1, self.ecfg.decode_chunk)
            if chunk == 1:
                (cache, state), packed = one((cache, state), None)
                packed = packed[None]
            else:
                (cache, state), packed = jax.lax.scan(
                    one, (cache, state), None, length=chunk)
        return cache, state, packed

    def _prefill_insert_fn(self, params, cache, state, tokens, slot, length,
                           budget):
        """prompt forward pass → first-token sample → slot insert → state
        update, one jitted cache-donated call per admission (sequential
        baseline + non-packable architectures)."""
        with activate_plan(self._plan):
            logits, pcache = T.prefill(params, self.cfg, {"tokens": tokens},
                                       impl=self.ecfg.impl,
                                       kv_cap=self.ecfg.kv_len, length=length,
                                       kv_bits=self.ecfg.kv_bits)
            nxt, key = self._sample_dev(logits, state["key"])
            tok = nxt[0]
            cache = self._insert_fn(cache, pcache, slot, length)
            state = {
                "tokens": state["tokens"].at[slot].set(tok),
                "pos": state["pos"].at[slot].set(length),
                "budget": state["budget"].at[slot].set(budget - 1),
                "live": state["live"].at[slot].set(budget > 1),
                "key": key,
            }
        return cache, state, tok

    def _insert_fn(self, cache, pcache, slot, length):
        """Insert a batch-1 prefill cache into slot ``slot`` of the pool
        with one ``dynamic_update_slice`` per leaf (batch axis is axis 1 of
        every stacked leaf).  ``pos`` entries at cache indices >= ``length``
        are invalidated so right-padding never leaves attendable entries
        (exact-length prefill makes it a no-op; ring caches only hold
        positions < length)."""
        def ins(path, pool, one):
            one = one.astype(pool.dtype)
            if str(getattr(path[-1], "key", "")) == "pos":
                idx = jnp.arange(one.shape[-1], dtype=jnp.int32)
                one = jnp.where(idx[None, None, :] < length, one, -1)
            start = (0, slot) + (0,) * (one.ndim - 2)
            return jax.lax.dynamic_update_slice(pool, one, start)

        return jax.tree_util.tree_map_with_path(ins, cache, pcache)

    def _packed_prefill_fn(self, params, cache, state, tokens, positions,
                           seg, gather_idx, seg_off, seg_len, final, budget,
                           active):
        """One ragged prefill for every admitted segment: packed forward
        pass (segment-masked attention) → per-segment first-token sample →
        one multi-slot scatter insert → state update.  Segment id == target
        slot index; ``active`` masks unused slots, ``final`` the segments
        whose prompt completed in this stream (non-final = first chunk of a
        long prompt, which only inserts KV)."""
        with activate_plan(self._prefill_plan):
            logits, pcache = T.prefill_packed(
                params, self.cfg, tokens, positions, seg, gather_idx,
                impl=self.ecfg.impl, kv_bits=self.ecfg.kv_bits)
        with activate_plan(self._plan):
            nxt, key = self._sample_dev(logits, state["key"])
            cache = self._packed_insert(cache, pcache["stack"], seg,
                                        positions, seg_len, active)
            fin = active & final
            state = {
                "tokens": jnp.where(fin, nxt, state["tokens"]),
                "pos": jnp.where(fin, seg_len, state["pos"]),
                "budget": jnp.where(fin, budget - 1, state["budget"]),
                "live": jnp.where(fin, budget > 1, state["live"]),
                "key": key,
            }
        return cache, state, jnp.where(fin, nxt, -1)

    def _packed_insert(self, cache, pstack, seg, positions, seg_len, active):
        """Scatter each packed segment into its KV slot — one scatter per
        cache leaf for the whole admission burst (replaces the per-request
        ``dynamic_update_slice`` loop).  Validity is governed entirely by
        the ``pos`` leaves, so those rows are rebuilt per slot (ring slot
        ``s`` of a cap-``c`` cache holds position ``p ≡ s (mod c)``,
        ``p ∈ [len-c, len)`` — identity layout for global caches), while
        k/v/latent leaves scatter the C packed tokens straight to their
        (slot, ring index) targets — O(C) work, independent of pool size."""
        B = self.ecfg.max_batch
        tgt = jnp.where(active, jnp.arange(B), B)       # B = dropped
        seg1 = seg[0]                                    # (C,) slot id, -1 pad
        pos1 = positions[0]                              # (C,) within-seg pos

        from repro.models.attention import ring_positions

        def ins(path, pool, packed):
            cap = pool.shape[2]
            if str(getattr(path[-1], "key", "")) == "pos":
                p = ring_positions(seg_len[:, None], cap)   # (B, cap)
                valid = (p >= 0) & active[:, None]
                rows = jnp.broadcast_to(
                    jnp.where(valid, p, -1)[None], (pool.shape[0], B, cap))
                return pool.at[:, tgt].set(rows, mode="drop")
            # only the last `cap` tokens of a segment survive its ring —
            # dropping the rest keeps scatter targets unique
            keep = (seg1 >= 0) & (pos1 >= jnp.take(seg_len, jnp.clip(seg1, 0),
                                                   mode="clip") - cap)
            row = jnp.where(keep, seg1, B)
            ring = jnp.where(keep, pos1 % cap, cap)
            return pool.at[:, row, ring].set(
                packed[:, 0].astype(pool.dtype), mode="drop")

        new_stack = [jax.tree_util.tree_map_with_path(ins, pool, packed)
                     for pool, packed in zip(cache["stack"], pstack)]
        return {"stack": new_stack}

    def _chunk_step_fn(self, params, cache, state, tokens, pos, take_idx,
                       final, budget):
        """One chunked-prefill continuation over the pool: write each
        prefilling row's next chunk into its cache at explicit positions,
        attend to the whole cache, and activate rows whose prompt completed
        (sample their first token)."""
        with activate_plan(self._plan):
            logits, cache = T.chunk_prefill_step(
                params, self.cfg, cache, tokens, pos, take_idx,
                impl=self.ecfg.impl)
            nxt, key = self._sample_dev(logits, state["key"])
            pos_end = jnp.max(jnp.where(pos >= 0, pos + 1, 0), axis=1)
            state = {
                "tokens": jnp.where(final, nxt, state["tokens"]),
                "pos": jnp.where(final, pos_end, state["pos"]),
                "budget": jnp.where(final, budget - 1, state["budget"]),
                "live": jnp.where(final, budget > 1, state["live"]),
                "key": key,
            }
        return cache, state, jnp.where(final, nxt, -1)

    # -- jitted cores: speculative decoding -----------------------------------
    def _spec_cols(self, cache, p):
        """Snapshot the ``spec_k + 1`` cache columns at ring indices
        ``(p + j) % cap`` of every leaf — everything a speculative step can
        write — so the rollback can scatter the pre-step bytes back for
        rejected positions.  Exact under ring aliasing because
        ``spec_k + 1 <= cap`` (validated at engine init) keeps the gathered
        indices of a row unique."""
        K1 = self.ecfg.spec_k + 1
        jj = jnp.arange(K1, dtype=jnp.int32)

        def take(pool):
            cap = pool.shape[2]
            ring = (p[:, None] + jj[None, :]) % cap          # (B, K1)
            bidx = jnp.arange(pool.shape[1])[:, None]
            return pool[:, bidx, ring]                       # (R, B, K1, ...)

        return jax.tree_util.tree_map(take, cache)

    def _spec_restore(self, cache, saved, p, mask):
        """Scatter saved columns back where ``mask`` (B, spec_k+1) is set —
        the jitted truncate-on-reject (and the pre-verify scratch wipe), one
        donation-friendly scatter per leaf."""
        K1 = self.ecfg.spec_k + 1
        jj = jnp.arange(K1, dtype=jnp.int32)

        def put(pool, sv):
            cap = pool.shape[2]
            ring = (p[:, None] + jj[None, :]) % cap
            bidx = jnp.arange(pool.shape[1])[:, None]
            cur = pool[:, bidx, ring]
            m = mask.reshape((1,) + mask.shape + (1,) * (cur.ndim - 3))
            return pool.at[:, bidx, ring].set(jnp.where(m, sv, cur))

        return jax.tree_util.tree_map(put, cache, saved)

    def _spec_step_fn(self, params, dparams, cache, dcache, state):
        """The speculative analogue of ``_fused_step_fn``: K sequential
        draft decode steps (draft params / draft cache), one batched
        ``verify_step`` scoring ``[t0, d_1..d_K]`` at positions
        ``p..p+K``, greedy or rejection-sampling acceptance, then a
        saved-column rollback of everything past the committed prefix.

        Commit accounting per live row: with ``n`` accepted drafts the step
        commits ``c = m + 1`` tokens ``[d_1..d_m, t_next]`` where
        ``m = min(n, budget-1, kv_len-1-pos, eos_idx)`` — the cache ends
        valid through ``pos + m`` (the K/V of every committed *input*) and
        the last committed token becomes the new pending token at
        ``pos + c``, exactly the invariant the non-speculative step
        maintains one token at a time.  Rows whose verify logits are
        non-finite are frozen bit-exactly (all K+1 columns restored, state
        untouched) and flagged on the anomaly channel.

        Returns ``(cache, dcache, state, packed)`` with ``packed`` a
        ``(spec_k+1, 4, B)`` int32 of (token | -1, done, anomaly,
        n_accepted) — still one host transfer per step."""
        ecfg = self.ecfg
        K, B = ecfg.spec_k, ecfg.max_batch
        K1 = K + 1
        live, p, t0 = state["live"], state["pos"], state["tokens"]
        self_draft = dcache is None
        jidx = jnp.arange(K1, dtype=jnp.int32)

        with activate_plan(self._plan):
            saved = self._spec_cols(cache, p)
            if not self_draft:
                dsaved = self._spec_cols(dcache, p)

            # -- draft: K sequential decode steps at draft precision --------
            def dstep(carry, _):
                dc, tok, dpos, key = carry
                pos_w = jnp.where(live, dpos, -1)
                logits, dc = T.decode_step(dparams, self.draft_cfg, dc, tok,
                                           pos_w, impl=ecfg.impl)
                nxt, key = self._sample_dev(logits, key)
                return (dc, nxt, dpos + 1, key), (nxt, logits)

            dc0 = cache if self_draft else dcache
            (dc1, _, _, key), (dtoks, dlogits) = jax.lax.scan(
                dstep, (dc0, t0, p, state["key"]), None, length=K)
            dtoks = dtoks.T                                  # (B, K)
            dlogits = jnp.swapaxes(dlogits, 0, 1)            # (B, K, V)
            if self_draft:
                # wipe the draft's scratch K/V: the verify chunk requires
                # every valid cache position strictly below the in-stream
                # block's, and the restore returns the exact pre-draft bytes
                cache = self._spec_restore(
                    dc1, saved, p, jnp.broadcast_to(live[:, None], (B, K1)))
            else:
                dcache = dc1

            # -- verify: score [t0, d_1..d_K] in one chunk call -------------
            vtoks = jnp.concatenate([t0[:, None], dtoks], axis=1)   # (B, K1)
            vpos = jnp.where(live[:, None], p[:, None] + jidx[None, :], -1)
            vlogits, cache = T.verify_step(params, self.cfg, cache, vtoks,
                                           vpos, impl=ecfg.impl)

            bad = ~jnp.all(jnp.isfinite(vlogits), axis=(1, 2))      # (B,)
            ok = live & ~bad

            # -- acceptance -------------------------------------------------
            if ecfg.temperature <= 0.0:
                tgt = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)
                acc = dtoks == tgt[:, :K]
                n = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1),
                            axis=1)
                t_next = jnp.take_along_axis(tgt, n[:, None], axis=1)[:, 0]
            else:
                # rejection sampling (Leviathan et al.): accept d_j iff
                # u * q(d_j) <= p(d_j); on first reject resample from the
                # normalised residual max(p - q, 0); q := 0 past the drafts
                # so full acceptance samples from the final target dist
                tau = ecfg.temperature
                qd = jax.nn.softmax(dlogits.astype(jnp.float32) / tau, -1)
                pt = jax.nn.softmax(vlogits.astype(jnp.float32) / tau, -1)
                q_at = jnp.take_along_axis(qd, dtoks[..., None], -1)[..., 0]
                p_at = jnp.take_along_axis(pt[:, :K], dtoks[..., None],
                                           -1)[..., 0]
                key, ku, kr = jax.random.split(key, 3)
                u = jax.random.uniform(ku, (B, K))
                acc = u * q_at <= p_at
                n = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1),
                            axis=1)
                p_n = jnp.take_along_axis(pt, n[:, None, None], axis=1)[:, 0]
                qpad = jnp.concatenate([qd, jnp.zeros_like(pt[:, :1])], 1)
                q_n = jnp.take_along_axis(qpad, n[:, None, None],
                                          axis=1)[:, 0]
                res = jnp.maximum(p_n - q_n, 0.0)
                res = res / jnp.maximum(res.sum(-1, keepdims=True), 1e-30)
                t_next = jax.random.categorical(
                    kr, jnp.log(jnp.maximum(res, 1e-30)), axis=-1
                ).astype(jnp.int32)

            # -- commit bound: budget, cache depth, eos ---------------------
            comm = jnp.concatenate([dtoks, jnp.zeros((B, 1), jnp.int32)], 1)
            comm = jnp.where(jidx[None, :] == n[:, None], t_next[:, None],
                             comm)
            m = jnp.minimum(n, state["budget"] - 1)
            m = jnp.minimum(m, ecfg.kv_len - 1 - p)
            eos_idx = jnp.full((B,), K1, jnp.int32)
            if ecfg.eos_token >= 0:
                eos_idx = jnp.min(jnp.where(comm == ecfg.eos_token,
                                            jidx[None, :], K1), axis=1)
                m = jnp.minimum(m, eos_idx)
            m = jnp.maximum(m, 0)

            # -- rollback past the committed prefix -------------------------
            mask = live[:, None] & ((jidx[None, :] > m[:, None])
                                    | bad[:, None])
            cache = self._spec_restore(cache, saved, p, mask)
            if not self_draft:
                # full acceptance leaves the draft cache one entry short
                # (input d_K at pos p+K was never drafted): one catch-up
                # decode step writes it, logits discarded
                cu_pos = jnp.where(ok & (m == K), p + K, -1)
                _, dcache = T.decode_step(dparams, self.draft_cfg, dcache,
                                          dtoks[:, K - 1], cu_pos,
                                          impl=ecfg.impl)
                dcache = self._spec_restore(dcache, dsaved, p, mask)

            # -- state update ----------------------------------------------
            c = jnp.where(ok, m + 1, 0)
            t_last = jnp.take_along_axis(comm, m[:, None], axis=1)[:, 0]
            pos_new = p + c
            budget_new = state["budget"] - c
            done = ok & ((budget_new <= 0) | (pos_new >= ecfg.kv_len)
                         | (eos_idx <= m))
            state = {
                "tokens": jnp.where(ok, t_last, state["tokens"]),
                "pos": pos_new,
                "budget": budget_new,
                "live": live & ~done,
                "key": key,
            }

            # -- packed host array (K+1, 4, B) ------------------------------
            tok_rows = jnp.where((jidx[:, None] <= m[None, :]) & ok[None, :],
                                 comm.T, -1)
            done_rows = ((jidx[:, None] == m[None, :])
                         & done[None, :]).astype(jnp.int32)
            row0 = (jidx[:, None] == 0)
            anom_rows = jnp.where(row0, (live & bad)[None, :], False)
            acc_rows = jnp.where(row0 & ok[None, :], n[None, :], 0)
            packed = jnp.stack([tok_rows, done_rows,
                                anom_rows.astype(jnp.int32), acc_rows],
                               axis=1)
        return cache, dcache, state, packed

    def _draft_prefill_fn(self, dparams, dcache, tokens, slot, length):
        """Batch-1 prompt prefill through the *draft* model, inserted into
        the draft KV pool — keeps the draft cache in lockstep with the
        target when a separate draft model speculates."""
        with activate_plan(self._prefill_plan):
            _, pcache = T.prefill(dparams, self.draft_cfg, {"tokens": tokens},
                                  impl=self.ecfg.impl,
                                  kv_cap=self.ecfg.kv_len, length=length)
        return self._insert_fn(dcache, pcache, slot, length)

    # -- jitted cores: seed-compat path ---------------------------------------
    def _decode_fn(self, params, cache, tokens, pos):
        logits, cache = T.decode_step(params, self.cfg, cache, tokens, pos,
                                      impl=self.ecfg.impl)
        return logits, cache

    def _prefill_fn(self, params, tokens, length):
        # single-request prefill padded to a bucketed length (static shape)
        logits, cache = T.prefill(params, self.cfg, {"tokens": tokens},
                                  impl=self.ecfg.impl, kv_cap=self.ecfg.kv_len,
                                  length=length, kv_bits=self.ecfg.kv_bits)
        return logits, cache
