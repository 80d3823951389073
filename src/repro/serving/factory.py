"""One place to assemble serving stacks: ``build_simulated_cluster`` for the
simulated multi-replica clock (per replica a private PrefixCache, a scheduler
wired to it, and a SimulatedExecutor sharing the same cache) and
``build_real_engine`` for a single-host real-JAX engine on either KV backend
(dense slots or the block-paged pool) — the pairings every driver
(launch/serve, benchmarks, examples, tests) needs."""
from __future__ import annotations

from typing import Optional

from repro.core.latency_model import BatchLatencyModel, a100_opt13b
from repro.core.policies import SCHEDULERS
from repro.core.priority import BatchLimits, DPUConfig
from repro.engine.prefix_cache import PrefixCache
from repro.engine.simulator import SimulatedExecutor
from repro.serving.cluster import Cluster
from repro.serving.router import Router


def build_simulated_cluster(num_replicas: int, scheduler: str = "relserve",
                            router_policy: str = "affinity_spill",
                            latency_model: Optional[BatchLatencyModel] = None,
                            limits: Optional[BatchLimits] = None,
                            dpu_config: Optional[DPUConfig] = None,
                            seed: int = 0, block_size: int = 16,
                            router: Optional[Router] = None,
                            kv_admission: str = "conservative",
                            prefix_sharing: bool = False,
                            engine_loop: str = "serial",
                            kv_tiering: bool = False, host_kv_cap: int = 0,
                            swap_bandwidth_gbps: float = 32.0,
                            proactive_offload: bool = False,
                            idle_horizon_s: Optional[float] = None,
                            swap_prefetch: bool = False,
                            debug_invariants: bool = False,
                            snapshot_every: int = 0) -> Cluster:
    lm = latency_model or a100_opt13b()
    caches = {}

    def make_scheduler(i: int):
        caches[i] = PrefixCache(block_size=block_size)
        kw = dict(limits=limits or BatchLimits(), latency_model=lm,
                  prefix_cache=caches[i], kv_admission=kv_admission,
                  prefix_sharing=prefix_sharing)
        if kv_tiering:
            kw.update(kv_tiering=True, host_kv_cap=host_kv_cap,
                      swap_bandwidth_gbps=swap_bandwidth_gbps,
                      proactive_offload=proactive_offload,
                      idle_horizon_s=idle_horizon_s,
                      swap_prefetch=swap_prefetch)
        if scheduler.startswith("relserve"):
            kw["dpu_config"] = dpu_config or DPUConfig()
        return SCHEDULERS[scheduler](**kw)

    def make_executor(i: int):
        return SimulatedExecutor(lm, prefix_cache=caches[i], seed=seed + i,
                                 swap_bandwidth_gbps=swap_bandwidth_gbps)

    return Cluster(make_scheduler, make_executor, num_replicas,
                   router=router or Router(num_replicas, policy=router_policy),
                   engine_loop=engine_loop, debug_invariants=debug_invariants,
                   snapshot_every=snapshot_every)


def build_real_engine(arch: str = "qwen3-1.7b", scheduler: str = "relserve",
                      kv_backend: str = "dense", *,
                      limits: Optional[BatchLimits] = None,
                      latency_model: Optional[BatchLatencyModel] = None,
                      dpu_config: Optional[DPUConfig] = None,
                      kv_admission: str = "conservative",
                      prefix_sharing: bool = False,
                      max_slots: int = 32, max_len: int = 512,
                      block_size: int = 16, num_blocks: Optional[int] = None,
                      seed: int = 0, model=None, params=None,
                      engine_loop: str = "serial",
                      kv_tiering: bool = False, host_kv_cap: int = 0,
                      swap_bandwidth_gbps: float = 32.0,
                      proactive_offload: bool = False,
                      idle_horizon_s: Optional[float] = None,
                      swap_prefetch: bool = False,
                      debug_invariants: bool = False, **executor_kw):
    """A single-replica real-JAX serving engine on the chosen KV backend.

    ``arch`` names the config (``get_config``): an arch id serves its
    published widths, ``'<arch>-smoke'`` the smoke-scale config the CPU
    tests run; weights are random from ``seed`` unless ``params`` is given.
    ``kv_backend='dense'`` is the per-slot baseline; ``'paged'`` runs the
    block-paged executor (BlockManager pools + paged-attention decode), with
    physically shared prefix blocks whenever the scheduler runs with
    ``prefix_sharing=True``. Pass ``model``/``params`` to reuse compiled
    functions across engines (e.g. the dense-vs-paged equivalence pin).
    """
    import jax

    from repro.configs import get_config
    from repro.engine.engine import ServingEngine
    from repro.engine.executor import make_real_executor

    from repro.models.registry import build_model

    if model is None:
        model = build_model(get_config(arch))
    if params is None:
        params = model.init_params(jax.random.PRNGKey(seed))
    pc = PrefixCache(block_size=block_size)
    limits = limits or BatchLimits()
    if num_blocks is None and kv_backend == "paged":
        # The scheduler charges the cap in raw tokens while the pool hands
        # out whole blocks — size the pool to cover the cap plus one block
        # of per-sequence rounding waste for a full decode batch, and never
        # below the dense layout's physical capacity. (A workload of many
        # tiny resident sequences can still out-fragment any fixed pool; the
        # executor's OutOfBlocks escalation stays as the loud backstop.)
        dense_equiv = -(-max_slots * max_len // block_size)
        cap_blocks = -(-limits.cap // block_size) + limits.max_num_seqs
        num_blocks = max(dense_equiv, cap_blocks)
    kw = dict(limits=limits, prefix_cache=pc,
              kv_admission=kv_admission, prefix_sharing=prefix_sharing)
    if kv_tiering:
        kw.update(kv_tiering=True, host_kv_cap=host_kv_cap,
                  swap_bandwidth_gbps=swap_bandwidth_gbps,
                  proactive_offload=proactive_offload,
                  idle_horizon_s=idle_horizon_s,
                  swap_prefetch=swap_prefetch)
    if latency_model is not None:
        kw["latency_model"] = latency_model
    if scheduler.startswith("relserve"):
        kw["dpu_config"] = dpu_config or DPUConfig()
    sched = SCHEDULERS[scheduler](**kw)
    num_host_blocks = 0
    if kv_tiering and kv_backend == "paged":
        # whole-block rounding: each swapped sequence wastes < 1 block, so
        # cap-in-blocks plus one block per possible resident sequence covers
        # any population the scheduler's token-granular host cap admits
        num_host_blocks = -(-host_kv_cap // block_size) + limits.max_num_seqs
    ex = make_real_executor(kv_backend, model, params, max_slots=max_slots,
                            max_len=max_len, prefix_cache=pc,
                            num_blocks=num_blocks, block_size=block_size,
                            share_prefix_blocks=prefix_sharing,
                            num_host_blocks=num_host_blocks, **executor_kw)
    return ServingEngine(sched, ex, engine_loop=engine_loop,
                         debug_invariants=debug_invariants)
