"""Dense decoder-only transformer (GQA, optional qk-norm / QKV-bias /
local:global sliding-window pattern). Also serves the VLM backbone (patch
embeddings prepended by the stub frontend).

Layer stacking uses ``lax.scan`` over *groups* of layers (a group is the
local:global repeat pattern — 1 for uniform archs, 6 for gemma3) so HLO stays
small and compile fast at 512 devices; the roofline harness compensates for
XLA's count-scan-body-once cost analysis compositionally (see DESIGN.md §3).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import GQALayout, ParallelConfig, gqa_layout
from repro.models import layers as L
from repro.models.param_utils import (
    abstract_params, count_params, init_params, param_shardings, param_specs, t,
)

LOCAL_ROPE_THETA = 10_000.0  # gemma3 uses short-rope on sliding-window layers

# Named scopes of the serving steps (metadata only: each op's name in the
# lowered program and the device trace carries its scope, the compiled code
# is the same): embed, qkv, kv_write, attn, mlp, logits.
scope = jax.named_scope


class DenseTransformer:
    """Functional model: params are an explicit pytree, methods are pure."""

    # prefill attention implementation: 'block' (pure-XLA blockwise flash,
    # the default) or 'flash' (the Pallas flash_prefill kernel — interpret
    # mode on CPU). Instance-level; see with_prefill_attn().
    prefill_attn_impl = "block"

    def __init__(self, cfg: ModelConfig, pc: Optional[ParallelConfig] = None):
        self.cfg = cfg
        self.pc = pc or ParallelConfig.single_device()
        self.layout: GQALayout = gqa_layout(cfg.num_heads, cfg.num_kv_heads, self.pc.tp)
        if cfg.attn_kind == "local_global":
            self.group = cfg.local_global_pattern + 1
            assert cfg.num_layers % self.group == 0
            self.kinds = ["local"] * cfg.local_global_pattern + ["global"]
        elif cfg.attn_kind == "swa":
            self.group, self.kinds = 1, ["local"]
        else:
            self.group, self.kinds = 1, ["global"]
        self.n_groups = cfg.num_layers // self.group
        self.full_idx = {p: i for i, p in enumerate(
            [p for p in range(self.group) if self.kinds[p] == "global"])}
        self.win_idx = {p: i for i, p in enumerate(
            [p for p in range(self.group) if self.kinds[p] == "local"])}
        self.n_full = len(self.full_idx)
        self.n_win = len(self.win_idx)
        self.embed_scale = math.sqrt(cfg.d_model) if "gemma" in cfg.name else 1.0

    # ---------------------------------------------------------------- params
    def templates(self):
        cfg, lay = self.cfg, self.layout
        G, Pg, D, F = self.n_groups, self.group, cfg.d_model, cfg.d_ff
        KVs, Qp, hd = lay.kv_slots, lay.q_per_slot, cfg.head_dim
        KV = lay.num_kv_heads
        qmask = jnp.asarray(lay.q_array() >= 0)          # [KVs, Qp] pad-slot mask
        dup = jnp.asarray(lay.dup_array())

        def init_wq(key):  # packed layout: zero weights on pad Q slots (exact math)
            w = jax.random.normal(key, (G, Pg, D, KVs, Qp, hd), jnp.float32) / math.sqrt(D)
            return w * qmask[None, None, None, :, :, None]

        def init_wo(key):
            w = jax.random.normal(key, (G, Pg, KVs, Qp, hd, D), jnp.float32) \
                / math.sqrt(lay.num_heads * hd)
            return w * qmask[None, None, :, :, None, None]

        def init_kv(key):  # canonical KV heads, then duplicate into slots
            w = jax.random.normal(key, (G, Pg, D, KV, hd), jnp.float32) / math.sqrt(D)
            return jnp.take(w, dup, axis=3)

        blocks: Dict[str, Any] = {
            "ln1": t((G, Pg, D), (None, None, None), "zeros"),
            "ln2": t((G, Pg, D), (None, None, None), "zeros"),
            "wq": t((G, Pg, D, KVs, Qp, hd), (None, None, None, "kv_heads", None, None),
                    custom=init_wq),
            "wk": t((G, Pg, D, KVs, hd), (None, None, None, "kv_heads", None), custom=init_kv),
            "wv": t((G, Pg, D, KVs, hd), (None, None, None, "kv_heads", None), custom=init_kv),
            "wo": t((G, Pg, KVs, Qp, hd, D), (None, None, "kv_heads", None, None, None),
                    custom=init_wo),
        }
        if cfg.qkv_bias:
            blocks["bq"] = t((G, Pg, KVs, Qp, hd), (None, None, "kv_heads", None, None), "zeros")
            blocks["bk"] = t((G, Pg, KVs, hd), (None, None, "kv_heads", None), "zeros")
            blocks["bv"] = t((G, Pg, KVs, hd), (None, None, "kv_heads", None), "zeros")
        if cfg.qk_norm:
            blocks["q_norm"] = t((G, Pg, hd), (None, None, None), "zeros")
            blocks["k_norm"] = t((G, Pg, hd), (None, None, None), "zeros")
        blocks.update(self._mlp_templates())
        Vp = cfg.padded_vocab(self.pc.tp)
        tree = {
            "embed": t((Vp, D), ("vocab", None), fan_in=D),
            "blocks": blocks,
            "final_norm": t((D,), (None,), "zeros"),
        }
        if not cfg.tie_embeddings:
            tree["lm_head"] = t((D, Vp), (None, "vocab"), fan_in=D)
        return tree

    def _mlp_templates(self):
        cfg = self.cfg
        G, Pg, D, F = self.n_groups, self.group, cfg.d_model, cfg.d_ff
        return {
            "w_gate": t((G, Pg, D, F), (None, None, None, "ff"), fan_in=D),
            "w_up": t((G, Pg, D, F), (None, None, None, "ff"), fan_in=D),
            "w_down": t((G, Pg, F, D), (None, None, "ff", None), fan_in=F),
        }

    def abstract_params(self):
        return abstract_params(self.templates(), self._dtype)

    def init_params(self, key):
        return init_params(self.templates(), key, self._dtype)

    def param_specs(self):
        return param_specs(self.templates(), self.pc)

    def param_shardings(self, mesh):
        return param_shardings(self.templates(), self.pc, mesh)

    def param_count(self) -> int:
        return count_params(self.templates())

    @property
    def _dtype(self):
        return jnp.dtype(self.cfg.dtype)

    # ---------------------------------------------------------------- cache
    def cache_struct(self, batch: int, max_len: int):
        """Abstract KV cache pytree for decode. Window layers use ring buffers."""
        cfg, lay = self.cfg, self.layout
        G, hd = self.n_groups, cfg.head_dim
        W = min(cfg.sliding_window or max_len, max_len)
        out = {}
        if self.n_full:
            shp = (G, self.n_full, batch, max_len, lay.kv_slots, hd)
            out["k_full"] = jax.ShapeDtypeStruct(shp, self._dtype)
            out["v_full"] = jax.ShapeDtypeStruct(shp, self._dtype)
        if self.n_win:
            shp = (G, self.n_win, batch, W, lay.kv_slots, hd)
            out["k_win"] = jax.ShapeDtypeStruct(shp, self._dtype)
            out["v_win"] = jax.ShapeDtypeStruct(shp, self._dtype)
        return out

    def init_cache(self, batch: int, max_len: int):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.cache_struct(batch, max_len))

    def cache_specs(self):
        spec = self.pc.spec(None, None, "batch", None, "kv_heads", None)
        return jax.tree.map(lambda _: spec, self.cache_struct(1, 1))

    # ---------------------------------------------------------------- paged cache
    def supports_paged(self) -> bool:
        """Whether the block-paged KV path covers this arch: every layer must
        be full (global) attention — ring-buffer window layers have no paged
        layout (yet), and hybrid/ssm families override this to False."""
        return self.n_win == 0

    def init_paged_pools(self, num_blocks: int, block_size: int):
        """Block-paged KV pools: one ``[num_blocks, KVs, block_size, hd]``
        K and V pool per layer, stacked over (group, layer-in-group) so the
        whole cache is two arrays. The kv-head axis sits ahead of the page
        axis so one head's page is a contiguous ``[block_size, hd]`` tile —
        the block ``paged_attention`` DMAs per grid step. Block id
        ``num_blocks - 1`` is conventionally the executor's scratch block
        (pad rows / pad table entries)."""
        if not self.supports_paged():
            raise NotImplementedError(
                f"{self.cfg.name}: paged KV supports full-attention archs only "
                f"(this arch has {self.n_win} window layer(s) per group)")
        shp = (self.n_groups, self.n_full, num_blocks, self.layout.kv_slots,
               block_size, self.cfg.head_dim)
        return {"k": jnp.zeros(shp, self._dtype),
                "v": jnp.zeros(shp, self._dtype)}

    def scatter_prefill_pools(self, pools, caches, block_tables):
        """Write a (padded, batched) prefill's dense per-sequence caches into
        the paged pools. ``caches`` is the ``prefill(...)`` cache pytree with
        k/v_full ``[G, n_full, B, L, KVs, hd]`` (L a multiple of block_size);
        ``block_tables`` ``[B, L // block_size]`` routes each block — pad rows
        and pad blocks should point at the scratch block."""
        bs = pools["k"].shape[4]
        with scope("kv_write"):
            for name in ("k", "v"):
                c = caches[f"{name}_full"]
                G, NF, B, L, KVs, hd = c.shape
                c = c.reshape(G, NF, B, L // bs, bs, KVs, hd).swapaxes(4, 5)
                pools[name] = pools[name].at[:, :, block_tables].set(
                    c.astype(pools[name].dtype))
        return pools

    def decode_step_paged(self, params, pools, tokens, positions,
                          block_tables, context_lens, *,
                          attn_impl: str = "ref"):
        """One decode step against the block-paged KV pools.

        tokens/positions: [B] int32; block_tables: [B, max_blocks] int32;
        context_lens: [B] int32 (== positions + 1 for live rows). Each layer
        scatters the new token's K/V into its pool at (block_tables[b,
        pos // bs], pos % bs) then attends through ``paged_attention``
        (``attn_impl='pallas'``/'pallas-interpret') or the pure-jnp reference
        (``'ref'`` — the CPU path the tests run). Returns (logits, pools);
        pools should be donated by the jit wrapper.
        """
        cfg = self.cfg
        bs = pools["k"].shape[4]
        B = tokens.shape[0]
        with scope("embed"):
            x = self.embed_tokens(params, tokens)
        pools = dict(pools)
        rows = jnp.arange(B)
        bids = block_tables[rows, positions // bs]
        offs = positions % bs
        for g in range(self.n_groups):
            pp = jax.tree.map(lambda a: a[g], params["blocks"])
            for p in range(self.group):
                with scope("qkv"):
                    h = L.rmsnorm(x, pp["ln1"][p], cfg.norm_eps)
                    q, k, v = self._qkv(pp, p, h, positions, "global")
                i = self.full_idx[p]
                with scope("kv_write"):
                    pools["k"] = self.write_token_kv(pools["k"], g, i, bids,
                                                     offs, k)
                    pools["v"] = self.write_token_kv(pools["v"], g, i, bids,
                                                     offs, v)
                with scope("attn"):
                    o = self._paged_attn(q, pools["k"][g, i], pools["v"][g, i],
                                         block_tables, context_lens,
                                         positions, attn_impl)
                    x = x + jnp.einsum("bgqh,gqhd->bd", o, pp["wo"][p])
                with scope("mlp"):
                    h = L.rmsnorm(x, pp["ln2"][p], cfg.norm_eps)
                    mlp, _ = self._mlp(pp, p, h)
                    x = x + mlp
                x = self._constrain(x, "batch", None)
        with scope("logits"):
            x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
            return self.logits(params, x), pools

    @staticmethod
    def write_token_kv(pool, g: int, i: int, block_ids, offsets, kv):
        """Write each row's new-token K (or V) ``kv`` [B, KVs, hd] into layer
        ``(g, i)`` of the stacked pool at slot ``offsets[b]`` of block
        ``block_ids[b]``; returns the updated pool.

        The KV-head dim is indexed too, so head_dim is the scatter's only
        window and XLA updates the (donated) pool in place in its own layout.
        Left as a window between index dims, the head dim makes XLA relayout
        and copy the whole stacked pool at every layer."""
        heads = jnp.arange(pool.shape[3])
        return pool.at[g, i, block_ids[:, None], heads[None, :],
                       offsets[:, None]].set(kv.astype(pool.dtype))

    @staticmethod
    def _paged_attn(q, k_pool, v_pool, block_tables, context_lens, positions,
                    attn_impl: str):
        """One layer's decode attention over its K/V pools."""
        from repro.kernels.paged_attention import paged_attention

        if attn_impl == "ref":
            # CPU path: gather the sequence's pages into a dense
            # [B, T, G, hd] view and run the *exact* dense decode recipe
            # (same dtype roundings, same masking) — paged and dense
            # backends then emit bit-identical tokens even in bf16, while T
            # stays the bucketed block span instead of max_len.
            kg = k_pool[block_tables].swapaxes(2, 3)
            vg = v_pool[block_tables].swapaxes(2, 3)
            Bq, NB, bsz, KVs, hd = kg.shape       # [B, NB, bs, KVs, hd]
            return L.decode_attention(
                q, kg.reshape(Bq, NB * bsz, KVs, hd),
                vg.reshape(Bq, NB * bsz, KVs, hd), positions)
        return paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                               interpret=attn_impl != "pallas")

    # ------------------------------------------------------------- building blocks
    def _constrain(self, x, *logical):
        if self.pc.dp_axes or self.pc.tp_axis:
            return jax.lax.with_sharding_constraint(x, self.pc.spec(*logical))
        return x

    def _qkv(self, pp, p: int, x, positions, kind: str):
        """x: [B, (S,) D] -> q [..., G, Qp, hd], k/v [..., G, hd] with rope applied."""
        cfg = self.cfg
        q = jnp.einsum("...d,dgqh->...gqh", x, pp["wq"][p])
        k = jnp.einsum("...d,dgh->...gh", x, pp["wk"][p])
        v = jnp.einsum("...d,dgh->...gh", x, pp["wv"][p])
        if cfg.qkv_bias:
            q = q + pp["bq"][p]
            k = k + pp["bk"][p]
            v = v + pp["bv"][p]
        if cfg.qk_norm:
            q = L.rmsnorm(q, pp["q_norm"][p], cfg.norm_eps)
            k = L.rmsnorm(k, pp["k_norm"][p], cfg.norm_eps)
        theta = LOCAL_ROPE_THETA if (kind == "local" and cfg.attn_kind == "local_global") \
            else cfg.rope_theta
        if x.ndim == 3:  # [B, S, D]
            q = L.apply_rope(q, positions[:, :, None, None], theta)
            k = L.apply_rope(k, positions[:, :, None], theta)
        else:            # [B, D] decode
            q = L.apply_rope(q, positions[:, None, None], theta)
            k = L.apply_rope(k, positions[:, None], theta)
        return q, k, v

    def _mlp(self, pp, p: int, x):
        out = L.swiglu_mlp(x, pp["w_gate"][p], pp["w_up"][p], pp["w_down"][p], self.cfg.act)
        return out, jnp.zeros((), jnp.float32)

    def _mixer_seq(self, pp, p: int, x, positions, seq_lens, kind: str, state):
        """Sequence-mode token mixer (attention). Returns (out, cache_entry)."""
        with scope("qkv"):
            q, k, v = self._qkv(pp, p, x, positions, kind)
        with scope("attn"):
            return self._attn_seq(pp, p, q, k, v, seq_lens, kind)

    def _attn_seq(self, pp, p: int, q, k, v, seq_lens, kind: str):
        """Sequence-mode attention and its output projection."""
        cfg = self.cfg
        window = cfg.sliding_window if kind == "local" else 0
        if self.prefill_attn_impl == "flash":
            # Pallas flash_prefill kernel: causal masking alone suffices for
            # ragged batches — rows past a sequence's length attend only pad
            # keys in their own causal past and are never read (the last-token
            # gather uses seq_lens). Layout swap: [B,S,G,Qp,hd] <-> [B,G,S,R,hd].
            from repro.kernels.flash_prefill import flash_prefill
            o = flash_prefill(jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
                              jnp.moveaxis(v, 1, 2), causal=True, window=window,
                              interpret=jax.default_backend() == "cpu")
            o = jnp.moveaxis(o, 2, 1)
        else:
            o = L.block_attention(q, k, v, causal=True, window=window,
                                  seq_lens=seq_lens)
        out = jnp.einsum("bsgqh,gqhd->bsd", o, pp["wo"][p])
        return out, (k, v)

    def with_prefill_attn(self, impl: str) -> "DenseTransformer":
        """A sibling model instance (same config/params pytree) whose prefill
        attention runs via ``impl`` ('block' | 'flash') — lets an executor opt
        into the kernel path without mutating a shared model object."""
        if impl not in ("block", "flash"):
            raise ValueError(f"unknown prefill attention impl {impl!r}")
        m = type(self)(self.cfg, self.pc)
        m.prefill_attn_impl = impl
        return m

    def _mixer_decode(self, pp, p: int, x, positions, kind: str, cache_kv):
        """cache_kv: (k_cache, v_cache) already containing the new token."""
        cfg = self.cfg
        q, k, v = self._qkv(pp, p, x, positions, kind)
        window = cfg.sliding_window if kind == "local" else 0
        kc, vc = cache_kv
        kc = L.cache_write(kc, k, positions, window=window)
        vc = L.cache_write(vc, v, positions, window=window)
        o = L.decode_attention(q, kc, vc, positions, window=window)
        out = jnp.einsum("bgqh,gqhd->bd", o, pp["wo"][p])
        return out, (kc, vc)

    def _attn_decode_inplace(self, pp, p: int, x, positions, kind: str,
                             cache, g: int):
        """Decode attention with scatter-in-place KV writes on the full cache."""
        cfg = self.cfg
        with scope("qkv"):
            q, k, v = self._qkv(pp, p, x, positions, kind)
        window = cfg.sliding_window if kind == "local" else 0
        if kind == "global":
            i, kk, vk = self.full_idx[p], "k_full", "v_full"
        else:
            i, kk, vk = self.win_idx[p], "k_win", "v_win"
        with scope("kv_write"):
            cache[kk] = L.cache_write_full(cache[kk], g, i, k, positions, window)
            cache[vk] = L.cache_write_full(cache[vk], g, i, v, positions, window)
        with scope("attn"):
            o = L.decode_attention(q, cache[kk][g, i], cache[vk][g, i],
                                   positions, window=window)
            out = jnp.einsum("bgqh,gqhd->bd", o, pp["wo"][p])
        return out, cache

    # ------------------------------------------------------------- forward (seq mode)
    def _group_seq(self, carry, pp, positions, seq_lens, collect: bool, max_len: int):
        x, aux = carry
        kf, vf, kw, vw = [], [], [], []
        cfg = self.cfg
        W = min(cfg.sliding_window or max_len, max_len)
        for p in range(self.group):
            kind = self.kinds[p]
            with scope("qkv"):
                h = L.rmsnorm(x, pp["ln1"][p], cfg.norm_eps)
            attn, (k, v) = self._mixer_seq(pp, p, h, positions, seq_lens, kind, None)
            x = x + attn
            with scope("mlp"):
                h = L.rmsnorm(x, pp["ln2"][p], cfg.norm_eps)
                mlp, a = self._mlp(pp, p, h)
                x = x + mlp
            aux = aux + a
            x = self._constrain(x, "batch", None, None)
            if not collect:
                continue
            with scope("kv_write"):
                if kind == "global":
                    S = k.shape[1]
                    pad = max_len - S
                    if pad:
                        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    kf.append(k)
                    vf.append(v)
                else:
                    kw.append(L.ring_from_sequence(k, W, seq_lens))
                    vw.append(L.ring_from_sequence(v, W, seq_lens))
        caches = {}
        with scope("kv_write"):
            if kf:
                caches["k_full"], caches["v_full"] = jnp.stack(kf), jnp.stack(vf)
            if kw:
                caches["k_win"], caches["v_win"] = jnp.stack(kw), jnp.stack(vw)
        return (x, aux), caches

    def forward_hidden(self, params, embeds, positions, seq_lens=None, *,
                       collect_cache=False, max_len: int = 0, remat=False):
        """embeds: [B, S, D] -> (hidden [B, S, D], aux, cache | {})."""
        cfg = self.cfg
        x = self._constrain(embeds, "batch", None, None)
        body = partial(self._group_seq, positions=positions, seq_lens=seq_lens,
                       collect=collect_cache, max_len=max_len or embeds.shape[1])
        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (x, aux), caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                        params["blocks"])
        x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return x, aux, caches

    def embed_tokens(self, params, tokens):
        e = jnp.take(params["embed"], tokens, axis=0)
        return (e * self.embed_scale).astype(self._dtype)

    def logits(self, params, hidden):
        if self.cfg.tie_embeddings:
            lg = jnp.einsum("...d,vd->...v", hidden, params["embed"])
        else:
            lg = hidden @ params["lm_head"]
        V, Vp = self.cfg.vocab_size, lg.shape[-1]
        if Vp > V:   # vocab padded to the TP multiple: mask pad columns
            lg = jnp.where(jnp.arange(Vp) < V, lg, L.NEG_INF)
        return lg

    # ------------------------------------------------------------- public steps
    def train_loss(self, params, batch, *, remat=True):
        """batch: {'tokens': [B,S_text], 'labels': [B,S_total] (-1 pad),
        'extra_embeds': optional [B,P,D] patch/frame stub embeddings}."""
        tokens = batch["tokens"]
        embeds = self.embed_tokens(params, tokens)
        if batch.get("extra_embeds") is not None:
            embeds = jnp.concatenate(
                [batch["extra_embeds"].astype(self._dtype), embeds], axis=1)
        B, S = embeds.shape[:2]
        positions = L.causal_positions(S, B)
        hidden, aux, _ = self.forward_hidden(params, embeds, positions, remat=remat)
        w_vocab = params["embed"].T if self.cfg.tie_embeddings else params["lm_head"]
        total, count = L.chunked_softmax_xent(hidden, w_vocab, batch["labels"],
                                              vocab_valid=self.cfg.vocab_size)
        loss = total / jnp.maximum(count, 1.0)
        loss = loss + self._aux_weight() * aux / max(1, self.cfg.num_layers)
        return loss, {"xent": total / jnp.maximum(count, 1.0), "aux": aux}

    def _aux_weight(self) -> float:
        return 0.0

    def prefill(self, params, tokens, *, seq_lens=None, max_len: int = 0,
                extra_embeds=None):
        """Returns (last-token logits [B, V], cache). ``extra_embeds`` are
        prepended patch/frame embeddings (VLM stub frontend)."""
        B, S_tok = tokens.shape
        with scope("embed"):
            embeds = self.embed_tokens(params, tokens)
        if extra_embeds is not None:
            embeds = jnp.concatenate([extra_embeds.astype(self._dtype), embeds], axis=1)
        S = embeds.shape[1]
        positions = L.causal_positions(S, B)
        max_len = max_len or S
        hidden, _, caches = self.forward_hidden(
            params, embeds, positions, seq_lens, collect_cache=True, max_len=max_len)
        with scope("logits"):
            if seq_lens is not None:
                last = jnp.take_along_axis(
                    hidden, (seq_lens - 1)[:, None, None].astype(jnp.int32),
                    axis=1)[:, 0]
            else:
                last = hidden[:, -1]
            return self.logits(params, last), caches

    def decode_step(self, params, cache, tokens, positions):
        """tokens: [B] int32, positions: [B] -> (logits [B, V], new cache).

        The layer loop is *unrolled* (decode graphs are small): each layer's
        KV write is an in-place dynamic-update-slice on the donated cache —
        no scan ys double-buffering of the multi-GB cache arrays.
        """
        cfg = self.cfg
        with scope("embed"):
            x = self.embed_tokens(params, tokens)
        cache = dict(cache)
        for g in range(self.n_groups):
            pp = jax.tree.map(lambda a: a[g], params["blocks"])
            for p in range(self.group):
                kind = self.kinds[p]
                with scope("qkv"):
                    h = L.rmsnorm(x, pp["ln1"][p], cfg.norm_eps)
                attn, cache = self._attn_decode_inplace(pp, p, h, positions,
                                                        kind, cache, g)
                x = x + attn
                with scope("mlp"):
                    h = L.rmsnorm(x, pp["ln2"][p], cfg.norm_eps)
                    mlp, _ = self._mlp(pp, p, h)
                    x = x + mlp
                x = self._constrain(x, "batch", None)
        with scope("logits"):
            x = L.rmsnorm(x, params["final_norm"], self.cfg.norm_eps)
            return self.logits(params, x), cache

    # ------------------------------------------------------------- roofline support
    def with_layers(self, num_layers: int) -> "DenseTransformer":
        """Same arch with a different layer count (roofline composition)."""
        return type(self)(self.cfg.replace(num_layers=num_layers), self.pc)

    @property
    def scan_trip_count(self) -> int:
        return self.n_groups

    @property
    def layers_per_scan_step(self) -> int:
        return self.group
