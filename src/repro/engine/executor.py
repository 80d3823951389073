"""Real JAX executors: token-by-token execution of scheduler-issued batches on
an actual model (smoke-scale configs on CPU, published widths on a TPU).

Two KV backends behind one engine-facing contract (``dispatch`` / ``wait`` —
with ``execute`` as the serial composition — plus ``release_request`` /
``validate_relquery`` / ``prestage`` / ``fitted_model``):

``RealExecutor`` — the dense baseline. ``max_slots`` decode cache slots of
``max_len`` tokens each (the model's dense/ring KV layout); prefill assigns
slots one request at a time with bucketed padding, decode runs one
``decode_step`` over all active slots. Kept bit-identical as the reference
the paged backend is pinned against.

``PagedRealExecutor`` — block-paged KV owned by ``BlockManager``: a single
``[num_blocks, heads, block_size, dim]`` K/V pool per layer, per-request
block tables, batched multi-request prefill (shape-bucketed on batch and
length to bound recompilation, through the Pallas ``flash_prefill`` kernel
on an accelerator) and decode through the Pallas ``paged_attention`` kernel
on an accelerator, or the gathered-page jnp recipe on CPU (the tests'
path). Prefix-sharing chains map to physically shared (ref-counted) blocks
with copy-on-write on divergence; preemption releases real blocks instead of
whole slots, so the scheduler's token ledger and device residency agree.

Both are the calibration source for the linear batch-cost model (paper
Fig. 7): ``fitted_model()`` fits α/β from measured (tokens, duration) /
(reqs, duration) samples on this host.
"""
from __future__ import annotations

import time as _time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import latency_model as lm_mod
from repro.core.batch import Batch
from repro.core.relquery import RelQuery, Request
from repro.core.scheduler import BatchResult
from repro.engine.kv_cache import BlockManager, OutOfBlocks
from repro.engine.prefix_cache import PrefixCache, block_hashes
from repro.engine.tracing import span, traced


class RequestCapacityError(ValueError):
    """A request can never fit this executor's per-sequence KV capacity —
    raised at admission (``EngineCore.admit``) instead of overflowing the
    slot buffer / block table mid-flight."""


@dataclass
class InFlight:
    """A dispatched-but-not-consumed batch: the device logits (JAX async
    futures until someone materializes them) plus the host bookkeeping
    ``wait`` needs to turn them into a ``BatchResult``.

    Splitting ``execute`` into ``dispatch`` (issue compiled calls, host-side
    KV bookkeeping) and ``wait`` (block on logits, sample, finish detection)
    lets the engine run the *next* scheduling decision while this batch is
    still on the device — ``jax.block_until_ready``/host transfer happens in
    ``wait``'s ``argmax`` materialization, not at dispatch."""
    batch: Batch
    # dense: [(req, logits)] per completing prefill; paged: [(group, logits)]
    prefill_pending: List
    decode_pending: Optional[object]     # decode-phase logits, or None
    decode_reqs: List[Request]
    decode_rows: List[int]               # dense: logits row per decode req
    utok: int                            # measured uncached prefill tokens
    prefill_issue_s: float               # host issue time, compile excluded
    decode_issue_s: float
    # produced-token count per req_id *as of dispatch* (this batch's token
    # included). The pipelined engine projects placeholder tokens onto
    # ``output_tokens`` while the batch is in flight, so ``wait`` must not
    # re-derive progress from live request state.
    produced: Dict[str, int] = field(default_factory=dict)


def _bucket(n: int, buckets=(16, 32, 64, 128, 256, 512, 1024, 2048, 4096)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return ((n + 4095) // 4096) * 4096


def _pow2_bucket(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


class _ExecutorBase:
    """Shared mechanics of the real executors: sampling, finish detection,
    admission-time capacity validation and cost-model calibration."""

    def __init__(self, model, params, *, max_len: int,
                 prefix_cache: Optional[PrefixCache] = None,
                 greedy: bool = True):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.prefix_cache = prefix_cache
        self.greedy = greedy
        self.prefill_samples: List[Tuple[int, float]] = []
        self.decode_samples: List[Tuple[int, float]] = []
        # every ahead-of-time compile this executor ran, and their seconds
        self.compile_count = 0
        self.compile_s_total = 0.0

    # ------------------------------------------------------------- admission
    def validate_relquery(self, rq: RelQuery) -> None:
        """Reject (at admission) any request whose worst-case prompt+output
        footprint can never fit a sequence's KV capacity — previously such a
        request silently overflowed the dense slot buffer mid-decode."""
        for r in rq.requests:
            need = r.num_prompt_tokens + r.max_output_tokens
            if need > self.max_len:
                raise RequestCapacityError(
                    f"request {r.req_id} of relQuery {rq.rel_id} needs up to "
                    f"{need} KV tokens (prompt {r.num_prompt_tokens} + "
                    f"max_output {r.max_output_tokens}) but this executor's "
                    f"per-sequence capacity is max_len={self.max_len}; "
                    f"shorten the prompt, lower max_output_tokens, or build "
                    f"the executor with a larger max_len")

    # ------------------------------------------------------------- shared bits
    def _aot(self, fn, *args, shape=None) -> Tuple[object, float]:
        """Ahead-of-time compile ``fn`` for ``args``; returns (executable,
        compile_seconds). Callers subtract the compile time from their
        measured phase duration: throughput samples and the fitted cost model
        must see steady-state execution, not first-shape XLA compilation
        (the shape-bucketed paged backend compiles several decode variants
        over a run — charging those to decode latency would skew both the
        clock and Fig. 7's α/β fit). The ``relserve.compile`` span names the
        program and its shape key (``shape``)."""
        with span("relserve.compile", program=fn.__name__, shape=str(shape)):
            t0 = _time.perf_counter()
            exe = fn.lower(*args).compile()
            dt = _time.perf_counter() - t0
        self.compile_count += 1
        self.compile_s_total += dt
        return exe, dt

    def _prefill_program(self, max_len: int):
        """The jitted prefill program, named ``prefill_step`` in its XLA
        module, for prompts padded to ``max_len``."""
        model = self.model

        def prefill_step(params, tokens, seq_lens):
            return model.prefill(params, tokens, seq_lens=seq_lens,
                                 max_len=max_len)
        return jax.jit(prefill_step)

    def _sample(self, logits) -> np.ndarray:
        return np.asarray(jnp.argmax(logits, axis=-1))

    @staticmethod
    def _release(req_ids: List[str], free) -> None:
        """Free what the requests that finished in this batch hold."""
        if req_ids:
            with span("relserve.release"):
                for req_id in req_ids:
                    free(req_id)

    def _is_finish_token(self, r: Request, tok: int, produced: int) -> bool:
        if r.eos_token is not None and tok == r.eos_token:
            return True
        return produced >= r.max_output_tokens

    def _account_prefill(self, r: Request, seq: Sequence[int]) -> int:
        """Prefix-cache stats identical across backends (count then insert,
        in batch order): only the prompt enters the cache — generated tokens
        are never prefix-cached (the estimator/PEM invariant)."""
        if self.prefix_cache is None:
            return len(seq)
        cached = self.prefix_cache.count_cached(seq)
        self.prefix_cache.insert(r.tokens)
        return len(seq) - cached

    # ------------------------------------------------------------- calibration
    def fitted_model(self):
        return lm_mod.fit(self.prefill_samples, self.decode_samples)


@dataclass
class Slot:
    req: Request
    position: int          # next decode position (== tokens written so far)


class RealExecutor(_ExecutorBase):
    """Dense per-slot KV backend (the bit-identical baseline)."""

    def __init__(self, model, params, *, max_slots: int = 32, max_len: int = 512,
                 prefix_cache: Optional[PrefixCache] = None, greedy: bool = True):
        super().__init__(model, params, max_len=max_len,
                         prefix_cache=prefix_cache, greedy=greedy)
        self.max_slots = max_slots
        self.cache = model.init_cache(max_slots, max_len)
        self.slots: List[Optional[Slot]] = [None] * max_slots
        self._slot_of: Dict[str, int] = {}
        self._prefill_fn = {}
        self._decode_fn = None
        self._decode_jit = jax.jit(model.decode_step, donate_argnums=(1,))
        # slot writes run jitted on the donated cache, in place: an eager
        # ``.at[].set`` would hold a second copy of the whole cache in HBM
        self._write_fn = {}
        self._put_fn = None
        self._compile_s = 0.0     # compile time to subtract from this batch
        # host KV tier: req_id -> (request, slot position, stashed cache
        # slice). Slices start as device arrays with an async device->host
        # copy issued at swap-out; the next wait() materializes them to
        # numpy, so the transfer overlaps the in-flight batch's compute.
        self._host_stash: Dict[str, Tuple[Request, int, object]] = {}
        self._pending_host: List[str] = []
        # swap-in prefetch: req_id -> device-resident copy of its stash,
        # staged ahead of the commit so the slot write pays no host->device
        # transfer (the stash itself stays authoritative until commit)
        self._prestaged: Dict[str, object] = {}

    # ------------------------------------------------------------------ slots
    def _alloc_slot(self, req: Request) -> int:
        for i, s in enumerate(self.slots):
            if s is None:
                self.slots[i] = Slot(req, 0)
                self._slot_of[req.req_id] = i
                return i
        raise RuntimeError("out of decode slots — scheduler exceeded max_num_seqs")

    def _free_slot(self, req_id: str) -> None:
        i = self._slot_of.pop(req_id, None)
        if i is not None:
            self.slots[i] = None

    def release_request(self, req_id: str) -> None:
        """Free executor-side state held for a request (its decode slot
        and/or host-tier stash). Called by the engine on cancellation/
        preemption; unknown req_ids are a no-op."""
        self._free_slot(req_id)
        self._host_stash.pop(req_id, None)
        self._prestaged.pop(req_id, None)

    # --------------------------------------------------------------- swapping
    def _slot_axis(self, arr) -> Optional[int]:
        """First axis carrying the per-slot dimension (same convention as
        ``_write_slot_cache``'s placement search); None for scalar-like cache
        entries shared by all slots."""
        for ax in range(arr.ndim):
            if arr.shape[ax] == self.max_slots:
                return ax
        return None

    def swap_out(self, req_id: str, tokens: int) -> float:
        """Stash ``req_id``'s dense KV slot on the host and free the slot.
        The device->host copy is issued async here and completed by the next
        ``wait()`` — it rides under the dispatched batch's compute, so the
        returned extra-seconds charge is 0.0. Unknown req_ids (already
        released, e.g. cancelled between the swap decision and its
        application) are a no-op."""
        i = self._slot_of.get(req_id)
        if i is None:
            return 0.0
        slot = self.slots[i]

        def take(leaf):
            ax = self._slot_axis(leaf)
            if ax is None:
                return "skip"   # string sentinel keeps the pytree structure
            idx = [slice(None)] * leaf.ndim
            idx[ax] = slice(i, i + 1)
            piece = leaf[tuple(idx)]
            copy = getattr(piece, "copy_to_host_async", None)
            if copy is not None:
                copy()
            return piece

        stash = jax.tree.map(take, self.cache)
        self._host_stash[req_id] = (slot.req, slot.position, stash)
        self._pending_host.append(req_id)
        self._free_slot(req_id)
        return 0.0

    def prefetch_swap_in(self, req_id: str, tokens: int) -> float:
        """Stage a stashed request's KV back onto the device ahead of its
        swap-in commit: the ``device_put`` is issued here, riding under the
        in-flight batch's compute, so the commit's slot write consumes an
        already-resident array instead of paying the host->device copy at
        dispatch. Unknown/already-staged req_ids are a no-op."""
        entry = self._host_stash.get(req_id)
        if entry is None or req_id in self._prestaged:
            return 0.0
        _, _, stash = entry
        self._prestaged[req_id] = jax.tree.map(
            lambda x: x if isinstance(x, str) else jax.device_put(x), stash)
        return 0.0

    def cancel_swap_prefetch(self, req_id: str, tokens: int) -> float:
        """Drop a staged prefetch whose request was cancelled before the
        swap-in commit (the authoritative host stash is freed by
        ``release_request``). Idempotent."""
        self._prestaged.pop(req_id, None)
        return 0.0

    def swap_in(self, req_id: str, tokens: int) -> float:
        """Restore a stashed request into a fresh slot (host->device write).
        The request resumes decoding at its stashed position — no re-prefill.
        A prefetched request's staged device copy is consumed instead of the
        host stash, skipping the transfer."""
        entry = self._host_stash.pop(req_id, None)
        if entry is None:
            return 0.0
        req, position, stash = entry
        staged = self._prestaged.pop(req_id, None)
        if staged is not None:
            stash = staged
        i = self._alloc_slot(req)
        # the "skip" sentinels of slot-less leaves become None (no argument)
        src = jax.tree.map(
            lambda x: None if isinstance(x, str) else jnp.asarray(x), stash)
        args = (self.cache, src, jnp.asarray(i, jnp.int32))
        if self._put_fn is None:
            def swap_put(cache, src, slot):
                return jax.tree.map(
                    lambda dst, x: dst if x is None else
                    jax.lax.dynamic_update_slice_in_dim(
                        dst, x.astype(dst.dtype), slot,
                        axis=self._slot_axis(dst)),
                    cache, src)
            self._put_fn, dt = self._aot(
                jax.jit(swap_put, donate_argnums=(0,)), *args)
            self._compile_s += dt
        self.cache = self._put_fn(*args)
        self.slots[i].position = position
        return 0.0

    def _materialize_host_stash(self) -> None:
        """Finish pending device->host stash transfers (called from ``wait``,
        after the batch's own blocking transfer — by then the async copies
        have landed and ``np.asarray`` is a cheap view materialization)."""
        for req_id in self._pending_host:
            entry = self._host_stash.get(req_id)
            if entry is None:
                continue    # released (cancel) before materialization
            req, position, stash = entry
            stash = jax.tree.map(
                lambda x: x if isinstance(x, str) else np.asarray(x), stash)
            self._host_stash[req_id] = (req, position, stash)
        self._pending_host = []

    # ------------------------------------------------------------------ prefill
    def _prefill_issue(self, req: Request) -> Tuple[object, int]:
        """Issue a request's prefill and write its KV into a slot; returns
        (device logits, utok) without sampling — the logits stay a device
        future until ``wait`` materializes them. For a preempted request's
        restart the pass recomputes prompt + preserved generation
        (recompute-style preemption recovery)."""
        seq = req.prefill_token_ids()
        n = len(seq)
        utok = self._account_prefill(req, seq)
        # pad-masked prefill (recurrent state frozen on pads); never pad past
        # the slot length — admission guarantees n <= max_len
        bucket = min(_bucket(n), self.max_len)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = seq
        args = (self.params, jnp.asarray(toks), jnp.asarray([n], jnp.int32))
        if bucket not in self._prefill_fn:
            self._prefill_fn[bucket], dt = self._aot(
                self._prefill_program(self.max_len), *args, shape=bucket)
            self._compile_s += dt
        logits, kv = self._prefill_fn[bucket](*args)
        slot = self._alloc_slot(req)
        self._write_slot_cache(slot, kv)
        self.slots[slot].position = n
        return logits, utok

    def prestage(self, batch: Batch) -> None:
        """Pre-compile the prefill shape buckets ``batch`` will need, with
        dummy-shaped arguments — called by the pipelined engine while the
        *previous* batch runs on the device, so a first-shape XLA compile
        never lands on the critical path. Decode/scatter functions are not
        pre-staged (they close over live cache shapes already compiled)."""
        for r in batch.prefill_requests:
            if not batch.completes_prompt(r):
                continue
            n = len(r.prefill_token_ids())
            bucket = min(_bucket(n), self.max_len)
            if bucket in self._prefill_fn:
                continue
            toks = np.zeros((1, bucket), np.int32)
            args = (self.params, jnp.asarray(toks),
                    jnp.asarray([n], jnp.int32))
            self._prefill_fn[bucket], _ = self._aot(
                self._prefill_program(self.max_len), *args, shape=bucket)

    def _write_slot_cache(self, slot: int, kv) -> None:
        """Copy a single-sequence prefill cache into slot ``slot``, in place
        on the donated cache (one compile per prefill cache shape)."""
        def write(dst, src, slot):
            if dst.ndim == src.ndim and dst.shape == src.shape:
                return src  # scalar-like entries (not per-slot)
            # batch dim location differs per model family; find the axis where
            # dst has max_slots and src has 1
            for ax in range(dst.ndim):
                if dst.shape[ax] == self.max_slots and src.shape[ax] == 1:
                    pad = [(0, d - s) if a != ax else (0, 0)
                           for a, (d, s) in enumerate(zip(dst.shape, src.shape))]
                    if any(p != (0, 0) for p in pad):
                        src = jnp.pad(src, pad)
                    return jax.lax.dynamic_update_slice_in_dim(
                        dst, src.astype(dst.dtype), slot, axis=ax)
            raise ValueError(f"cannot place prefill cache {src.shape} into {dst.shape}")

        args = (self.cache, kv, jnp.asarray(slot, jnp.int32))
        key = tuple(x.shape for x in jax.tree.leaves(kv))
        if key not in self._write_fn:
            def slot_write(cache, kv, slot):
                return jax.tree.map(lambda d, s: write(d, s, slot), cache, kv)
            self._write_fn[key], dt = self._aot(
                jax.jit(slot_write, donate_argnums=(0,)), *args, shape=key)
            self._compile_s += dt
        self.cache = self._write_fn[key](*args)

    # ------------------------------------------------------------------ decode
    def _decode_issue(self, reqs: List[Request]) -> object:
        tokens = np.zeros((self.max_slots,), np.int32)
        positions = np.zeros((self.max_slots,), np.int32)
        # decode_step scatters every row's K/V at positions[i] — rows must
        # never default to (token 0, position 0), which silently corrupted
        # position 0 of any occupied slot outside the scheduled batch (e.g. a
        # request prefilled earlier in the same mixed batch). Point occupied
        # off-batch rows at their own next position with their own last token:
        # for attention caches the write is idempotent (the slot's real
        # decode rewrites the same values) and the row's logits are discarded
        # below. Recurrent families (hymba's SSM/conv state) still advance
        # off-batch rows — a pre-existing limitation of whole-batch
        # decode_step that needs a per-row freeze mask to fix; the scheduler
        # only leaves a slot out of a decode batch in the same tick that
        # prefilled it, so attention archs are exact.
        for i, s in enumerate(self.slots):
            if s is not None:
                tokens[i] = s.req.output_tokens[-1] if s.req.output_tokens else 0
                positions[i] = s.position
        for r in reqs:
            i = self._slot_of[r.req_id]
            tokens[i] = r.output_tokens[-1] if r.output_tokens else 0
            positions[i] = self.slots[i].position
        args = (self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(positions))
        if self._decode_fn is None:
            self._decode_fn, dt = self._aot(self._decode_jit, *args,
                                            shape=self.max_slots)
            self._compile_s += dt
        logits, self.cache = self._decode_fn(*args)
        for r in reqs:
            self.slots[self._slot_of[r.req_id]].position += 1
        return logits

    # ------------------------------------------------------------------ engine API
    @traced("relserve.dispatch")
    def dispatch(self, batch: Batch, now: float) -> InFlight:
        """Issue one unified batch on the device without blocking: prefill
        passes write their KV and the decode step advances the slot
        positions, but no logits are materialized on the host. Prefill and
        decode issue times are kept separate so ``wait`` can complete the
        phase-separated samples ``fitted_model()`` calibration expects."""
        self._compile_s = 0.0
        t0 = _time.perf_counter()
        pending = []
        total_utok = 0
        with span("relserve.prefill_issue"):
            for r in batch.prefill_requests:
                if not batch.completes_prompt(r):
                    continue  # chunk not finishing the prompt: accounted only
                logits, utok = self._prefill_issue(r)
                total_utok += utok
                pending.append((r, logits))
        prefill_issue = max(0.0, _time.perf_counter() - t0 - self._compile_s)
        reqs = [r for r in batch.decode_requests if r.req_id in self._slot_of]
        decode_logits, rows, decode_issue = None, [], 0.0
        if reqs:
            self._compile_s = 0.0
            t1 = _time.perf_counter()
            with span("relserve.decode_issue"):
                decode_logits = self._decode_issue(reqs)
            # capture logits rows now: a prefill request finishing in wait()
            # frees its own slot only, so these stay valid either way
            rows = [self._slot_of[r.req_id] for r in reqs]
            decode_issue = max(0.0,
                               _time.perf_counter() - t1 - self._compile_s)
        produced = {r.req_id: len(r.output_tokens) + 1
                    for r in (*(p[0] for p in pending), *reqs)}
        return InFlight(batch=batch, prefill_pending=pending,
                        decode_pending=decode_logits, decode_reqs=reqs,
                        decode_rows=rows, utok=total_utok,
                        prefill_issue_s=prefill_issue,
                        decode_issue_s=decode_issue, produced=produced)

    @traced("relserve.wait")
    def wait(self, inflight: InFlight) -> Tuple[float, BatchResult]:
        """Materialize a dispatched batch: sample every pending logits row
        (the blocking host transfer), detect finishes and free their slots.
        Returns the same (duration, BatchResult) contract as ``execute`` —
        durations cover issue + wait, compile time excluded."""
        outputs: Dict[str, Tuple[int, bool]] = {}
        prefill_dur = inflight.prefill_issue_s
        if inflight.prefill_pending:
            t0 = _time.perf_counter()
            with span("relserve.prefill_sync"):
                toks = [int(self._sample(logits)[0])
                        for _, logits in inflight.prefill_pending]
            done = []
            for (r, _), tok in zip(inflight.prefill_pending, toks):
                # a restarted (preempted) request already produced its
                # preserved tokens; this prefill emits the (len + 1)-th
                finished = self._is_finish_token(r, tok,
                                                 inflight.produced[r.req_id])
                outputs[r.req_id] = (tok, finished)
                if finished:
                    done.append(r.req_id)
            self._release(done, self._free_slot)
            prefill_dur += _time.perf_counter() - t0
            self.prefill_samples.append((inflight.utok, prefill_dur))
        decode_dur = inflight.decode_issue_s
        if inflight.decode_pending is not None:
            t1 = _time.perf_counter()
            with span("relserve.decode_sync"):
                out = self._sample(inflight.decode_pending)
            done = []
            for r, row in zip(inflight.decode_reqs, inflight.decode_rows):
                tok = int(out[row])
                # ``produced`` was counted at dispatch, when output_tokens
                # held only *landed* iterations — matching the simulated
                # executor's count even if a speculative placeholder has
                # been projected onto the request since.
                finished = self._is_finish_token(r, tok,
                                                 inflight.produced[r.req_id])
                outputs[r.req_id] = (tok, finished)
                if finished:
                    done.append(r.req_id)
            self._release(done, self._free_slot)
            decode_dur += _time.perf_counter() - t1
            self.decode_samples.append((len(inflight.decode_reqs), decode_dur))
        with span("relserve.host_stash"):
            self._materialize_host_stash()
        return prefill_dur + decode_dur, BatchResult(outputs)

    def execute(self, batch: Batch, now: float) -> Tuple[float, BatchResult]:
        """Serial composition of the split contract — the serial engine loop
        and older callers see the exact pre-split behavior."""
        return self.wait(self.dispatch(batch, now))


class PagedRealExecutor(_ExecutorBase):
    """Block-paged KV backend: ``BlockManager``-owned pools, per-request
    block tables, batched bucketed prefill and paged-attention decode.

    The last pool block (id ``num_blocks``) is a scratch page: pad rows and
    pad table entries route there, so fixed-shape scatters never touch live
    blocks. KV demand agrees with the scheduler's token ledger: a request
    resident from prefill completion to finish/preempt/cancel, shared prefix
    chains (``share_prefix_blocks=True``, paired with the scheduler's
    ``prefix_sharing``) held once and ref-counted, copy-on-write if a write
    ever lands in a block a sibling still references.
    """

    def __init__(self, model, params, *, num_blocks: int = 1024,
                 block_size: int = 16, max_len: int = 512,
                 prefix_cache: Optional[PrefixCache] = None,
                 greedy: bool = True, attn_impl: Optional[str] = None,
                 prefill_attn: Optional[str] = None,
                 share_prefix_blocks: bool = False,
                 num_host_blocks: int = 0):
        if not getattr(model, "supports_paged", lambda: False)():
            raise NotImplementedError(
                f"model {model.cfg.name!r} does not support the paged KV "
                f"backend (full-attention transformer families only); use "
                f"kv_backend='dense'")
        on_cpu = jax.default_backend() == "cpu"
        if prefill_attn is None:
            prefill_attn = "block" if on_cpu else "flash"
        if prefill_attn == "flash":
            model = model.with_prefill_attn("flash")
        super().__init__(model, params, max_len=max_len,
                         prefix_cache=prefix_cache, greedy=greedy)
        # Pallas on a real accelerator; on CPU (the tests' path) the jnp
        # recipe that keeps paged and dense streams bit-identical;
        # 'pallas-interpret' forces the kernel through the interpreter for
        # parity debugging.
        self.attn_impl = attn_impl or ("ref" if on_cpu else "pallas")
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.scratch_block = num_blocks          # pools hold one extra page
        self.max_blocks_per_seq = -(-max_len // block_size)
        self.share_prefix_blocks = share_prefix_blocks
        self.num_host_blocks = num_host_blocks
        self.bm = BlockManager(num_blocks, block_size=block_size,
                               num_host_blocks=num_host_blocks)
        self.pools = model.init_paged_pools(num_blocks + 1, block_size)
        self._active: Dict[str, Request] = {}
        # host KV tier: req_id -> (request, {"k": blocks, "v": blocks}) with
        # blocks gathered along the pool's block axis in table order. Device
        # arrays with an async device->host copy at swap-out, numpy after the
        # next wait() materializes them (transfer overlapped with compute).
        self._host_stash: Dict[str, Tuple[Request, Dict[str, object]]] = {}
        self._pending_host: List[str] = []
        # swap-in prefetch: req_id -> staged copy plan; the blocks were
        # written at prefetch time, so the commit is pure accounting
        self._staged_swap_in: Dict[str, List[Tuple[int, int]]] = {}
        self._prefill_fn: Dict[Tuple[int, int], object] = {}
        self._scatter_fn: Dict[Tuple[int, int], object] = {}
        self._decode_fn: Dict[Tuple[int, int], object] = {}
        self._copy_fn = None
        self.cow_copies = 0
        self.shared_block_hits = 0    # physically shared prefix blocks reused
        self._compile_s = 0.0     # compile time to subtract from this batch

    # ------------------------------------------------------------- admission
    def validate_relquery(self, rq: RelQuery) -> None:
        """Beyond the per-sequence ``max_len`` bound, a request must also fit
        the *pool*: a footprint needing more blocks than the pool holds could
        never prefill no matter what else is evicted."""
        super().validate_relquery(rq)
        for r in rq.requests:
            need = r.num_prompt_tokens + r.max_output_tokens
            blocks = self.bm.blocks_needed(need)
            if blocks > self.num_blocks:
                raise RequestCapacityError(
                    f"request {r.req_id} of relQuery {rq.rel_id} needs "
                    f"{blocks} KV blocks (footprint {need} tokens / "
                    f"block_size {self.block_size}) but the paged pool holds "
                    f"only num_blocks={self.num_blocks}; grow the pool or "
                    f"shrink the request")

    # ------------------------------------------------------------- bookkeeping
    def release_request(self, req_id: str) -> None:
        """Free the request's blocks (cancellation/preemption): real paged
        reclamation — siblings still referencing shared prefix blocks keep
        them alive; only the last reference returns a block to the free list.
        Frees whichever tier(s) hold the request — a swapped request's host
        blocks and stash go too."""
        known = self._active.pop(req_id, None) is not None
        known = (self._host_stash.pop(req_id, None) is not None) or known
        self._staged_swap_in.pop(req_id, None)
        if known:
            self.bm.free(req_id)   # staged prefetch blocks go back too

    # --------------------------------------------------------------- swapping
    def swap_out(self, req_id: str, tokens: int) -> float:
        """Move ``req_id``'s blocks to the host tier per the BlockManager's
        copy plan. Every block is gathered (shared prefix blocks included —
        the host image is self-contained) before the manager drops the device
        references, so a block a sibling still references stays resident and
        is never freed here. The device->host copy is issued async and
        completed by the next ``wait()``; returns 0.0 (overlapped)."""
        r = self._active.pop(req_id, None)
        if r is None:
            return 0.0
        plan = self.bm.swap_out(req_id)        # [(device_bid, host_bid)]
        dev = jnp.asarray([d for d, _ in plan], jnp.int32)
        data: Dict[str, object] = {}
        for name in ("k", "v"):
            piece = jnp.take(self.pools[name], dev, axis=2)
            copy = getattr(piece, "copy_to_host_async", None)
            if copy is not None:
                copy()
            data[name] = piece
        self._host_stash[req_id] = (r, data)
        self._pending_host.append(req_id)
        return 0.0

    def prefetch_swap_in(self, req_id: str, tokens: int) -> float:
        """Stage a swapped request's host image into freshly-allocated device
        blocks ahead of the swap-in commit — the pool writes happen here,
        riding under the in-flight batch's compute, so the commit is pure
        accounting. No-op when the request is unknown, already staged, or
        the pool lacks free blocks (the commit falls back to the synchronous
        path)."""
        entry = self._host_stash.get(req_id)
        if entry is None or req_id in self._staged_swap_in:
            return 0.0
        plan = self.bm.prefetch_swap_in(req_id)
        if plan is None:
            return 0.0
        _, data = entry
        dst = jnp.asarray([d for _, d in plan], jnp.int32)
        for name in ("k", "v"):
            src = jnp.asarray(data[name]).astype(self.pools[name].dtype)
            self.pools[name] = self.pools[name].at[:, :, dst].set(src)
        self._staged_swap_in[req_id] = plan
        return 0.0

    def cancel_swap_prefetch(self, req_id: str, tokens: int) -> float:
        """Return a staged prefetch's device blocks (the request was
        cancelled before commit). The pool bytes written at staging are
        simply orphaned — freed blocks are always rewritten before reuse.
        Idempotent."""
        if self._staged_swap_in.pop(req_id, None) is not None:
            self.bm.cancel_prefetch(req_id)
        return 0.0

    def swap_in(self, req_id: str, tokens: int) -> float:
        """Restore a swapped request into fresh private device blocks (its
        shared-prefix identity was dropped at swap-out) and resume decode at
        its stashed context length — no re-prefill. A prefetched request's
        blocks were already allocated and written at staging, so its commit
        skips the copy entirely."""
        entry = self._host_stash.pop(req_id, None)
        if entry is None:
            return 0.0
        r, data = entry
        if self._staged_swap_in.pop(req_id, None) is not None:
            self.bm.commit_prefetch(req_id)
            self._active[req_id] = r
            return 0.0
        plan = self.bm.swap_in(req_id)         # [(host_bid, device_bid)]
        dst = jnp.asarray([d for _, d in plan], jnp.int32)
        for name in ("k", "v"):
            src = jnp.asarray(data[name]).astype(self.pools[name].dtype)
            self.pools[name] = self.pools[name].at[:, :, dst].set(src)
        self._active[req_id] = r
        return 0.0

    def _materialize_host_stash(self) -> None:
        """Finish pending device->host stash transfers (from ``wait``, after
        the batch's own blocking transfer — the async copies have landed)."""
        for req_id in self._pending_host:
            entry = self._host_stash.get(req_id)
            if entry is None:
                continue    # released (cancel) before materialization
            r, data = entry
            self._host_stash[req_id] = (
                r, {n: np.asarray(a) for n, a in data.items()})
        self._pending_host = []

    def kv_tokens_resident(self) -> int:
        """Per-sequence resident tokens: shared prefix blocks count once per
        referencing sequence — i.e. the scheduler's *raw* optimistic charge
        (`tokens_in_use`) before the `SharedPrefixLedger` discount. Physical
        pool occupancy is lower by exactly that discount when sharing is on."""
        return self.bm.tokens_in_use()

    def _prompt_keys(self, r: Request) -> Tuple[int, ...]:
        return tuple(block_hashes(r.tokens, self.block_size))

    def _prefill_group_key(self, r: Request) -> int:
        """Block-aligned length bucket a request prefills under (the same
        per-request bucket the dense baseline pads to — keeping per-row
        numerics identical across backends, bf16 included)."""
        L = min(_bucket(len(r.prefill_token_ids())), self.max_len)
        return -(-L // self.block_size) * self.block_size

    def prestage(self, batch: Batch) -> None:
        """Pre-compile the (batch, length) prefill buckets ``batch`` will
        group into, with dummy-shaped arguments — run by the pipelined engine
        under the previous batch's device compute. Scatter/decode functions
        are not pre-staged: their argument shapes depend on live pool/cache
        values only available at dispatch."""
        groups: Dict[int, int] = {}
        for r in batch.prefill_requests:
            if batch.completes_prompt(r):
                L = self._prefill_group_key(r)
                groups[L] = groups.get(L, 0) + 1
        for L, n in sorted(groups.items()):
            key = (_pow2_bucket(n), L)
            if key in self._prefill_fn:
                continue
            B = key[0]
            toks = np.zeros((B, L), np.int32)
            args = (self.params, jnp.asarray(toks),
                    jnp.asarray(np.ones((B,), np.int32)))
            self._prefill_fn[key], _ = self._aot(self._prefill_program(L),
                                                 *args, shape=key)

    # ------------------------------------------------------------- prefill
    def _prefill_issue_batch(self, reqs: List[Request]) -> Tuple[List, int]:
        """Batched multi-request prefill, shape-bucketed on (batch, length):
        each group runs as one model call followed by one scatter into the
        pools. Returns ([(group requests, device logits)], utok) — sampling
        deferred to ``wait``."""
        seqs = {r.req_id: r.prefill_token_ids() for r in reqs}
        utok = 0
        for r in reqs:                      # accounting in dense batch order
            utok += self._account_prefill(r, seqs[r.req_id])
        bs = self.block_size
        groups: Dict[int, List[Request]] = {}
        for r in reqs:
            groups.setdefault(self._prefill_group_key(r), []).append(r)
        pending: List = []
        for L in sorted(groups):
            grp = groups[L]
            B = _pow2_bucket(len(grp))
            nblk = L // bs
            toks = np.zeros((B, L), np.int32)
            seq_lens = np.ones((B,), np.int32)
            tables = np.full((B, nblk), self.scratch_block, np.int32)
            for i, r in enumerate(grp):
                seq = seqs[r.req_id]
                n = len(seq)
                toks[i, :n] = seq
                seq_lens[i] = n
                keys = self._prompt_keys(r) if self.share_prefix_blocks else ()
                try:
                    alloc = self.bm.allocate(r.req_id, n, prefix_keys=keys)
                    self.shared_block_hits += alloc.shared_prefix_blocks
                except OutOfBlocks as e:
                    raise RuntimeError(
                        f"paged KV pool exhausted during prefill of "
                        f"{r.req_id}: {e} — the scheduler's cap admitted more "
                        f"resident tokens than num_blocks*block_size covers"
                    ) from e
                if keys:
                    self.bm.register_prefix(r.req_id, keys)
                self._active[r.req_id] = r
                row = self.bm.padded_block_table(r.req_id, nblk,
                                                 self.scratch_block)
                # a follower must never rewrite pages its leader already
                # owns: the leader may be mid-decode attending them, and on
                # kernel backends the recomputed bytes are not bit-identical
                # — shared leading pages are written exactly once (by the
                # leader), so route the follower's scatter there to scratch
                for j in range(alloc.shared_prefix_blocks):
                    row[j] = self.scratch_block
                tables[i] = row
            key = (B, L)
            args = (self.params, jnp.asarray(toks), jnp.asarray(seq_lens))
            if key not in self._prefill_fn:
                self._prefill_fn[key], dt = self._aot(
                    self._prefill_program(L), *args, shape=key)
                self._compile_s += dt
            logits, caches = self._prefill_fn[key](*args)
            sargs = (self.pools, caches, jnp.asarray(tables))
            if key not in self._scatter_fn:
                model = self.model

                def scatter_prefill(pools, caches, block_tables):
                    return model.scatter_prefill_pools(pools, caches,
                                                       block_tables)
                self._scatter_fn[key], dt = self._aot(
                    jax.jit(scatter_prefill, donate_argnums=(0,)), *sargs,
                    shape=key)
                self._compile_s += dt
            self.pools = self._scatter_fn[key](*sargs)
            pending.append((grp, logits))
        return pending, utok

    # ------------------------------------------------------------- decode
    def _copy_block(self, src: int, dst: int) -> None:
        """Device-side CoW: clone page ``src`` into ``dst`` across all layers
        before the diverging write."""
        args = (self.pools, jnp.asarray(src, jnp.int32),
                jnp.asarray(dst, jnp.int32))
        if self._copy_fn is None:
            def copy_block(pools, s, d):
                pools = dict(pools)
                for name in ("k", "v"):
                    pools[name] = jax.lax.dynamic_update_index_in_dim(
                        pools[name],
                        jax.lax.dynamic_index_in_dim(pools[name], s, axis=2,
                                                     keepdims=False),
                        d, axis=2)
                return pools
            self._copy_fn, dt = self._aot(
                jax.jit(copy_block, donate_argnums=(0,)), *args)
            self._compile_s += dt
        self.pools = self._copy_fn(*args)
        self.cow_copies += 1

    def _decode_issue(self, reqs: List[Request]) -> object:
        bs = self.block_size
        positions = []
        for r in reqs:
            pos = self.bm.context_len(r.req_id)
            positions.append(pos)
            try:
                _, cow = self.bm.append_token_cow(r.req_id)
            except OutOfBlocks as e:
                raise RuntimeError(
                    f"paged KV pool exhausted during decode of {r.req_id}: "
                    f"{e}") from e
            if cow is not None:
                self._copy_block(*cow)
        width = max(len(self.bm.block_table(r.req_id)) for r in reqs)
        NB = min(_pow2_bucket(width), self.max_blocks_per_seq)
        NB = max(NB, width)
        B = _pow2_bucket(len(reqs))
        tokens = np.zeros((B,), np.int32)
        pos_arr = np.zeros((B,), np.int32)
        ctx = np.ones((B,), np.int32)
        tables = np.full((B, NB), self.scratch_block, np.int32)
        for i, (r, pos) in enumerate(zip(reqs, positions)):
            tokens[i] = r.output_tokens[-1] if r.output_tokens else 0
            pos_arr[i] = pos
            ctx[i] = pos + 1
            tables[i] = self.bm.padded_block_table(r.req_id, NB,
                                                   self.scratch_block)
        key = (B, NB)
        args = (self.params, self.pools, jnp.asarray(tokens),
                jnp.asarray(pos_arr), jnp.asarray(tables), jnp.asarray(ctx))
        if key not in self._decode_fn:
            model, impl = self.model, self.attn_impl

            def decode_step(params, pools, tokens, positions, block_tables,
                            context_lens):
                return model.decode_step_paged(
                    params, pools, tokens, positions, block_tables,
                    context_lens, attn_impl=impl)
            self._decode_fn[key], dt = self._aot(
                jax.jit(decode_step, donate_argnums=(1,)), *args, shape=key)
            self._compile_s += dt
        logits, self.pools = self._decode_fn[key](*args)
        return logits

    # ------------------------------------------------------------- engine API
    @traced("relserve.dispatch")
    def dispatch(self, batch: Batch, now: float) -> InFlight:
        """Issue one unified batch: block allocation, prefill + pool scatter
        and the paged decode step all run host-side/async; logits stay on the
        device until ``wait``. Block frees of requests finishing in this
        batch happen in ``wait`` — at a near-exhausted pool this defers a
        handful of frees by one phase, which can surface ``OutOfBlocks``
        slightly earlier than the fused loop did (the scheduler's cap keeps
        real configurations away from that boundary)."""
        prefill_reqs = [r for r in batch.prefill_requests
                        if batch.completes_prompt(r)]
        pending: List = []
        utok = 0
        prefill_issue = 0.0
        if prefill_reqs:
            self._compile_s = 0.0
            t0 = _time.perf_counter()
            with span("relserve.prefill_issue"):
                pending, utok = self._prefill_issue_batch(prefill_reqs)
            prefill_issue = max(0.0,
                                _time.perf_counter() - t0 - self._compile_s)
        reqs = [r for r in batch.decode_requests if r.req_id in self._active]
        decode_logits, decode_issue = None, 0.0
        if reqs:
            self._compile_s = 0.0
            t1 = _time.perf_counter()
            with span("relserve.decode_issue"):
                decode_logits = self._decode_issue(reqs)
            decode_issue = max(0.0,
                               _time.perf_counter() - t1 - self._compile_s)
        produced = {r.req_id: len(r.output_tokens) + 1
                    for r in (*(r for grp, _ in pending for r in grp), *reqs)}
        return InFlight(batch=batch, prefill_pending=pending,
                        decode_pending=decode_logits, decode_reqs=reqs,
                        decode_rows=[], utok=utok,
                        prefill_issue_s=prefill_issue,
                        decode_issue_s=decode_issue, produced=produced)

    @traced("relserve.wait")
    def wait(self, inflight: InFlight) -> Tuple[float, BatchResult]:
        """Same phase-separated timing contract as the dense executor:
        sample each prefill group then the decode step, free the blocks of
        anything that finished."""
        outputs: Dict[str, Tuple[int, bool]] = {}
        prefill_dur = inflight.prefill_issue_s
        if inflight.prefill_pending:
            t0 = _time.perf_counter()
            with span("relserve.prefill_sync"):
                sampled = [self._sample(logits)
                           for _, logits in inflight.prefill_pending]
            done = []
            for (grp, _), out_tokens in zip(inflight.prefill_pending, sampled):
                for i, r in enumerate(grp):
                    tok = int(out_tokens[i])
                    finished = self._is_finish_token(r, tok,
                                                     inflight.produced[r.req_id])
                    outputs[r.req_id] = (tok, finished)
                    if finished:
                        done.append(r.req_id)
            self._release(done, self.release_request)
            prefill_dur += _time.perf_counter() - t0
            self.prefill_samples.append((inflight.utok, prefill_dur))
        decode_dur = inflight.decode_issue_s
        if inflight.decode_pending is not None:
            t1 = _time.perf_counter()
            with span("relserve.decode_sync"):
                out = self._sample(inflight.decode_pending)
            done = []
            for i, r in enumerate(inflight.decode_reqs):
                tok = int(out[i])
                finished = self._is_finish_token(r, tok,
                                                 inflight.produced[r.req_id])
                outputs[r.req_id] = (tok, finished)
                if finished:
                    done.append(r.req_id)
            self._release(done, self.release_request)
            decode_dur += _time.perf_counter() - t1
            self.decode_samples.append((len(inflight.decode_reqs), decode_dur))
        with span("relserve.host_stash"):
            self._materialize_host_stash()
        return prefill_dur + decode_dur, BatchResult(outputs)

    def execute(self, batch: Batch, now: float) -> Tuple[float, BatchResult]:
        """Serial composition of the split contract."""
        return self.wait(self.dispatch(batch, now))


KV_BACKENDS = ("dense", "paged")


def make_real_executor(kv_backend: str, model, params, *, max_slots: int = 32,
                       max_len: int = 512,
                       prefix_cache: Optional[PrefixCache] = None,
                       num_blocks: Optional[int] = None, block_size: int = 16,
                       share_prefix_blocks: bool = False,
                       num_host_blocks: int = 0, **kw):
    """Build a real executor by backend name. ``num_blocks`` defaults to the
    dense layout's physical capacity (max_slots × max_len worth of tokens) so
    switching backends never shrinks device KV. ``num_host_blocks`` sizes the
    paged backend's host swap tier (the dense backend's host stash is
    per-slot and needs no sizing)."""
    if kv_backend == "dense":
        return RealExecutor(model, params, max_slots=max_slots,
                            max_len=max_len, prefix_cache=prefix_cache, **kw)
    if kv_backend == "paged":
        if num_blocks is None:
            num_blocks = -(-max_slots * max_len // block_size)
        return PagedRealExecutor(model, params, num_blocks=num_blocks,
                                 block_size=block_size, max_len=max_len,
                                 prefix_cache=prefix_cache,
                                 share_prefix_blocks=share_prefix_blocks,
                                 num_host_blocks=num_host_blocks, **kw)
    raise ValueError(f"unknown kv_backend {kv_backend!r}; expected one of "
                     f"{KV_BACKENDS}")
