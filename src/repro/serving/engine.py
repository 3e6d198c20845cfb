"""S-HPLB serving engine: plan-driven sparse prefill + budgeted decode,
continuous batching, sampling.

The engine owns:
- the offline artifacts: sparsity profile -> HPLB plan (budgets +
  head permutation) -> per-layer work-lists / decode block budgets;
- the device state: HPLB-permuted params, and the KV cache: a paged block
  pool [L, 2, N+1, Hkv, block, Dh] addressed through per-sequence block
  tables (token-granular HBM — DESIGN.md §2.7);
- the jitted step functions (prefill with sparse work-lists; decode with
  budgeted block streams; per-sequence positions for continuous batching).

Attention modes:
    "dense"  — full attention (the FlashAttention baseline of the paper);
    "sparse" — S-HPLB: adaptive budgets + balanced work-lists.

The plan is EPOCH-VERSIONED (DESIGN.md §2.9), not an init-time constant:
an :class:`~repro.core.sparsity.OnlineSparsityEstimator` accumulates
Quest-bound estimates of each head's *realized* recovery on the decode hot
path (``telemetry_every``), drift against the offline profile triggers —
or ``replan_every`` forces — an in-flight replan at a scheduler safe
point: budgets re-derive incrementally (warm-started max-min), the new
placement is applied as a composable permutation delta to the params
host-side plus ONE kv-head gather over the resident cache, and every
memoized planning artifact is keyed by ``(epoch, ...)`` so the old epoch
ages out of the bounded caches lazily while requests keep flowing.

On a single host this runs real tokens end-to-end (examples/, tests/); under
a production mesh the same engine code paths lower with shard_map islands
(see ``launch.steps`` for the dry-run wiring).
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.attention.policies import policy_by_name
from repro.core import quant
from repro.core.planner import (
    HPLBPlan,
    make_plan,
    permute_attention_params,
    plan_delta,
    plans_equal,
)
from repro.core.sparsity import HeadSparsityProfile, OnlineSparsityEstimator
from repro.core.worklist import (
    DEC_FIELDS,
    WorkList,
    blocks_for_budget,
    chunk_item_counts,
    chunk_items,
    extend_packed_items,
    pack_decode_items,
    pack_decode_items_2d,
    pow2_bucket,
    worklist_from_budgets,
)
from repro.models import transformer as tfm
from repro.models.transformer import TransformerConfig
from repro.serving.faults import (
    EpochSwapError,
    FaultInjector,
    IntegrityError,
    TransferError,
)
from repro.serving.kv_cache import PagedKVCache
from repro.serving.prefix_tree import RadixPrefixCache
from repro.serving.sampler import SamplingParams, sample
from repro.serving.scheduler import ContinuousBatcher, Request
from repro.utils.logging import get_logger

log = get_logger("engine")


@dataclasses.dataclass
class EngineConfig:
    attention: str = "sparse"        # "sparse" (S-HPLB) | "dense"
    policy: str = "strided"          # static selection policy
    budget_per_head: int = 512       # k — the uniform-equivalent budget
    block: int = 128
    floor: int = 128
    allocator: str = "maxmin"        # paper | "uniform" (top-k baseline)
    partitioner: str = "best"        # "best" | "lpt" (paper) | "naive"
    num_model_shards: int = 1        # HP degree for planning
    max_seq_len: int = 4096
    num_slots: int = 8
    # prefill compile-bucket policy: "pow2" pads prompts up to the next
    # power of two (compile count O(log max_seq_len)); "exact" compiles one
    # program per distinct prompt length (the old behavior).
    prefill_buckets: str = "pow2"
    # chunked prefill (Sarathi-style mixed ticks): each scheduler tick runs
    # at most one prefill chunk of <= prefill_chunk_tokens alongside the
    # full decode batch, so admissions never stall decodes.  "monolithic"
    # prefills whole prompts at admission (the old behavior; kept as the
    # benchmark baseline).
    prefill_mode: str = "chunked"    # "chunked" | "monolithic"
    prefill_chunk_tokens: int = 256  # per-tick token budget (chunk cap)
    # the KV cache is a paged block pool with per-sequence block tables
    # (DESIGN.md §2.7); "paged" is the only accepted value.  The field
    # stays because the benchmark configs still name it.
    cache_layout: str = "paged"
    # pool size in blocks; None = num_slots * max_seq_len / block (every
    # slot can hold a max_seq_len sequence).  Smaller pools trade
    # worst-case capacity for HBM; admission guards via reservations.
    num_kv_blocks: int | None = None
    # KV storage dtype (DESIGN.md §2.12): "bf16" keeps the full-precision
    # cache bitwise-unchanged; "int8" / "fp8" store quantized codes plus a
    # per-(block, kv-head) f32 dequant scale and dequantize INSIDE the
    # decode kernels (post-dot rescale — no f32 cache copy ever
    # materializes).  Quantization halves (vs bf16) the resident bytes per
    # token AND the swap/preempt host-tier bandwidth.
    kv_dtype: str = "bf16"
    # decode work layout (DESIGN.md §2.8): "packed" flattens each tick's
    # per-slot selections into a cost-packed ragged worklist (grid length
    # = total selected blocks rounded to a pow2 bucket — scales with
    # mean_h b_h); "padded" pads every head's selection to the max-budget
    # width (the step-invariant baseline; grid scales with max_h b_h).
    # Both produce bitwise-identical greedy tokens.
    decode_worklist: str = "packed"  # "packed" | "padded"
    # -- plan epochs (DESIGN.md §2.9) ------------------------------------
    # online telemetry cadence: every N decode ticks one un-donated probe
    # estimates each head's realized recovery (Quest block bounds) and
    # folds it into the OnlineSparsityEstimator.  0 disables telemetry.
    telemetry_every: int = 0
    # replan policy: force a replan every N decode ticks, and/or replan
    # when the online profile's drift vs the offline one reaches the
    # threshold (drift needs telemetry_every > 0).  Both None = frozen
    # plan (the pre-epoch behavior).  Swaps only happen at scheduler safe
    # points (no prefill chunks straddling the epoch boundary).
    replan_every: int | None = None
    drift_threshold: float | None = None
    # LRU caps on the compiled-step memos: epoch swaps retire old-epoch
    # programs lazily (eviction), never eagerly (in-flight dispatch).
    prefill_jit_cap: int = 16
    chunk_jit_cap: int = 16
    # -- overload robustness (DESIGN.md §2.10) ----------------------------
    # admission policy: "fifo" (class-blind arrival order — the baseline)
    # or "slo" (class-level order + cost-model deferral + deadline shed).
    admission: str = "fifo"
    # allow preemption of strictly-lower-priority work when a request
    # cannot be placed: decoding victims swap their mapped KV blocks to a
    # pinned-host tier and resume later bitwise-identically; mid-prefill
    # victims are discarded back to their queue head.
    preemption: bool = False
    # host swap-tier capacity in blocks (None = unbounded).
    host_swap_blocks: int | None = None
    # -- sequence-parallel long context (DESIGN.md §2.11) -----------------
    # number of seq-axis stripes the paged pool is split into: each stripe
    # owns a contiguous block-id range, decode runs one partial attention
    # pass per stripe and merges (out, m, l) — the single-host emulation of
    # the 2D (model x seq) mesh's per-device islands.  1 = the 1D head-
    # parallel path, bitwise-unchanged.
    seq_shards: int = 1
    # -- fault tolerance (DESIGN.md §2.13) --------------------------------
    # per-tick numerical sentinels: after every prefill/decode step the
    # engine checks the sampled slots' logits for NaN/Inf (a host copy of
    # the whole logits array, reduced with numpy) and quarantines ONLY the
    # poisoned sequence.
    sentinels: bool = True
    # host swap transfers retry with exponential backoff before giving up
    # (give-up surfaces TransferError -> scheduler discard-and-requeue)
    swap_retries: int = 3
    swap_backoff_s: float = 0.0       # base backoff (0 = no sleep, tests)
    # allocator invariant audit cadence: every N decode ticks (plus swap
    # and replan boundaries); 0 = boundaries only.  Violations raise a
    # structured IntegrityError instead of silently serving corrupt state.
    audit_every: int = 0
    # crash-consistent checkpoints (serving/snapshot.py): every N decode
    # ticks, at a replan-safe boundary, snapshot engine + allocator +
    # scheduler + host-tier + plan state.  None / 0 = disabled.
    checkpoint_dir: str | None = None
    checkpoint_every: int = 0
    # -- radix-tree prefix cache (DESIGN.md §2.14) ------------------------
    # content-hash radix tree over full prompt blocks: admission maps the
    # longest cached prefix for free (refcounted block sharing in the
    # paged pool; prefill starts at the divergence block) and unreferenced
    # subtrees LRU-evict under pool pressure BEFORE preemption kicks in.
    # Greedy decoding stays bitwise identical to prefix_cache=False.
    prefix_cache: bool = False


class Engine:
    """Single-model serving engine (transformer-family archs)."""

    def __init__(self, cfg: TransformerConfig, params, engine_cfg: EngineConfig,
                 profile: HeadSparsityProfile | None = None,
                 injector: FaultInjector | None = None):
        if engine_cfg.cache_layout != "paged":
            raise ValueError(
                f"cache_layout {engine_cfg.cache_layout!r}: the contiguous "
                "slot cache was removed; the paged pool is the only layout")
        # attention tile sizes MUST match the work-list granularity: items
        # address (head, q_blk, kv_blk) tiles in units of ``engine_cfg.block``,
        # and a kernel running wider tiles slices/writes past the buffer —
        # dynamic_update_slice CLAMPS the out-of-range start and silently
        # clobbers tile 0 (exposed by any run whose chunk boundaries differ
        # from its comparison baseline, e.g. a prefix-cache hit)
        if (cfg.block_q != engine_cfg.block
                or cfg.block_kv != engine_cfg.block):
            cfg = dataclasses.replace(cfg, block_q=engine_cfg.block,
                                      block_kv=engine_cfg.block)
        self.cfg = cfg
        self.ecfg = engine_cfg
        # fault injection (DESIGN.md §2.13): every seam below guards on
        # ``injector is None or not injector.enabled`` before touching
        # anything, so a run without an injector is bitwise-identical to a
        # build without the fault layer
        self.injector = injector
        # slots flagged by the numerical sentinels this step, drained by
        # the scheduler (sentinel_fn) right after the step returns
        self._quarantine: dict[int, str] = {}
        self.fault_stats = {
            "sentinel_trips": 0,       # slots quarantined by sentinels
            "swap_faults": 0,          # transfer attempts that faulted
            "swap_retries": 0,         # retry attempts issued
            "swap_recoveries": 0,      # transfers healed by a retry
            "swap_giveups": 0,         # retries exhausted -> TransferError
            "audits": 0,               # invariant audits run (all passed)
            "replan_rollbacks": 0,     # epoch swaps rolled back
            "corruptions_injected": 0,  # kv_corrupt seam firings
            "checkpoints": 0,          # snapshots written
        }
        self._last_audit_activity = -1  # forces an audit on the first tick
        self.plan: HPLBPlan | None = None
        self.profile = profile          # offline profile
        # the profile the LIVE plan was derived from — the drift
        # reference (== the offline profile until the first replan; after
        # a swap, drift is measured against the new plan's basis so a
        # one-time shift cannot re-trigger forever)
        self._plan_profile = profile
        self.epoch = 0                  # live plan-epoch (DESIGN.md §2.9)
        self.telemetry: OnlineSparsityEstimator | None = None
        if engine_cfg.attention == "sparse":
            assert profile is not None, "sparse mode needs a sparsity profile"
            self.plan = make_plan(
                profile,
                num_devices=engine_cfg.num_model_shards,
                num_kv_heads=cfg.num_kv_heads,
                seq_len=engine_cfg.max_seq_len,
                total_budget_per_head=engine_cfg.budget_per_head,
                block=engine_cfg.block,
                floor=engine_cfg.floor,
                allocator=engine_cfg.allocator,
                partitioner=engine_cfg.partitioner,
                epoch=0,
            )
            params = self._permute_params(params)
            self.telemetry = OnlineSparsityEstimator(
                cfg.num_layers, cfg.num_heads)
        self.params = params
        # every memoized planning artifact below is keyed by (epoch, ...):
        # an epoch swap re-derives on demand and old-epoch entries either
        # age out of the LRU memos or are purged (plain dicts)
        self._worklists_cache: dict[tuple, list] = {}
        # quantized KV (DESIGN.md §2.12): validate the dtype name up front
        # (is_quantized raises on unknown); "bf16" leaves every path below
        # exactly as before — scales never exist, steps return 2-tuples
        self.quantized = quant.is_quantized(engine_cfg.kv_dtype)
        # byte-true packing weight (§2.12): real HBM bytes one selected kv
        # block streams at decode (K+V codes + amortized per-block scales)
        self._kv_block_bytes = (
            2.0 * engine_cfg.block * cfg.head_dim_
            * quant.kv_dtype_bytes(engine_cfg.kv_dtype,
                                   block=engine_cfg.block,
                                   head_dim=cfg.head_dim_))
        # radix prefix cache (DESIGN.md §2.14): built by make_batcher so
        # it shares the batcher's allocator wiring; None = sharing off
        self.prefix = None
        assert engine_cfg.max_seq_len % engine_cfg.block == 0, \
            "paged layout needs max_seq_len % block == 0"
        nblocks = (engine_cfg.num_kv_blocks
                   or engine_cfg.num_slots
                   * (engine_cfg.max_seq_len // engine_cfg.block))
        # stripes must tile the pool exactly: round the usable block count
        # UP to a seq_shards multiple (never down — capacity is an
        # admission guarantee)
        ss = engine_cfg.seq_shards
        nblocks = -(-nblocks // ss) * ss
        self.kv = PagedKVCache(
            lambda n: tfm.init_paged_cache(
                cfg, n, engine_cfg.block,
                dtype=quant.kv_cache_dtype(engine_cfg.kv_dtype)),
            num_blocks=nblocks, block=engine_cfg.block,
            table_width=engine_cfg.max_seq_len // engine_cfg.block,
            host_blocks=engine_cfg.host_swap_blocks,
            stripes=engine_cfg.seq_shards,
            make_scales_fn=((lambda n: tfm.init_paged_scales(cfg, n))
                            if self.quantized else None))
        # the allocator fires the admission_alloc seam mid-_grow
        self.kv.alloc.injector = injector
        # self.cache is the LIVE pool threaded through the jitted steps
        # (donated); self.kv keeps the allocator/tables and is re-pointed
        # at the new buffer after every step.  Quantized: the donated unit
        # is the (codes, scales) PAIR — every step that moves a block
        # moves its scale in the same program.
        self.cache = ((self.kv.pool, self.kv.scales) if self.quantized
                      else self.kv.pool)
        self._batcher = None   # bound by make_batcher (table lookups)
        # prefill compiled-step memos: LRU-bounded OrderedDicts (PR-4's
        # packed-plan discipline) — monolithic prefill BAKES the epoch's
        # work-lists into the program, so its key carries the epoch and an
        # epoch swap must not leak stale compiled entries
        self._prefill_jit: OrderedDict = OrderedDict()
        # chunked prefill: one compile per chunk bucket (pow2 from block up
        # to prefill_chunk_tokens); chunk work-lists enter as DATA padded to
        # a per-bucket item cap, so chunk offsets (and epochs) never
        # recompile.  Each chunk scatters into its sequence's own pool
        # blocks, which decode never reads before the final chunk.
        self._prefill_chunk_jit: OrderedDict = OrderedDict()
        self._chunk_cap: dict[tuple, int] = {}
        self._chunk_wl_cache: dict[tuple, np.ndarray] = {}
        self._decode_jit = None
        # cost-packed ragged decode (DESIGN.md §2.8): plans (packed item
        # tables) are memoized by the tick's per-slot BLOCK-COUNT signature
        # (selections depend only on block counts, so consecutive ticks hit
        # until a slot crosses a boundary), LRU-bounded; jitted steps are
        # keyed by the flat pow2 item bucket (O(log worst-case) compiles).
        self._decode_packed_jit: dict[int, object] = {}
        self._packed_plan_cache: OrderedDict = OrderedDict()
        self._packed_plan_cap = 256
        # per-tick decode bubble telemetry (padding_waste / imbalance of
        # the executed grid vs the padded baseline) — see decode_bubble_stats
        self.decode_stats = {"ticks": 0, "real_items": 0, "grid_items": 0,
                             "padded_grid_items": 0, "imbalance_sum": 0.0,
                             "head_imb_sum": 0.0, "stripe_imb_sum": 0.0,
                             "merge_collectives": 0,
                             "plan_hits": 0, "plan_misses": 0,
                             "plan_prefetches": 0, "last": {}}
        self._rng = jax.random.PRNGKey(0)
        # position-aware decode selection: ids depend only on the slot's
        # current BLOCK count, so they are recomputed exactly at block
        # boundaries and memoized per (epoch, block count).  _nb_cap fixes
        # the padded width PER EPOCH so changing selections never change
        # shapes within an epoch (no recompiles).
        self._decode_ids_by_nblocks: dict[tuple, np.ndarray] = {}
        self._nb_cap: dict[int, int] = {}
        # plan-epoch machinery (DESIGN.md §2.9)
        self._telemetry_jit: dict[int, object] = {}
        self._kv_permute_jit = None
        # preemption swap-to-host tier (DESIGN.md §2.10): host copies of
        # swapped-out sequences' KV blocks, keyed by rid.  _kv_arrange
        # tracks the CUMULATIVE kv-head arrangement of the resident cache
        # across plan epochs (arrange[l, h] = original kv head living in
        # slot h) so a host copy taken under one epoch can be re-arranged
        # EXACTLY ONCE at swap-in, however many epoch swaps passed.
        self._host_swaps: dict[int, dict] = {}
        self._swap_gather_jit: dict[int, object] = {}
        self._swap_scatter_jit: dict[int, object] = {}
        self._kv_arrange = np.tile(np.arange(cfg.num_kv_heads),
                                   (cfg.num_layers, 1))
        self.swap_stats = {"swapped_out": 0, "swapped_in": 0,
                           "blocks_out": 0, "blocks_in": 0,
                           "bytes_out": 0, "bytes_in": 0,
                           "epoch_remaps": 0}
        self._decode_ticks = 0
        self._ticks_since_replan = 0
        self._epoch_stats: dict[int, dict] = {0: self._fresh_epoch_stats()}
        self._last_drift: dict | None = None
        self.replans = 0
        # the pool is exclusively engine-owned and threaded through
        # every jitted step, so it is always donated: XLA CPU aliases
        # donated buffers since jax 0.4.x (measured ~200x on the in-place
        # cache update), and backends without aliasing degrade to a copy
        # with a warning — never an error.
        self._donate = True

    # -- offline artifacts -------------------------------------------------
    def _fresh_epoch_stats(self) -> dict:
        return {"ticks": 0, "telemetry_samples": 0,
                "recovery_sum": 0.0, "recovery_ticks": 0, "drift": None}

    def _permute_params(self, params, layer_plans=None,
                        kv_replicated: bool | None = None):
        """Apply a head permutation to the attention weights (host-side).

        Default: the engine plan's full original->slot permutation (init
        path).  ``layer_plans`` overrides with per-layer permutations —
        epoch swaps pass the :class:`~repro.core.planner.PlanDelta` layers
        here, re-permuting the ALREADY-permuted weights in place; the
        jitted step functions never re-trace (same shapes, new buffers).
        """
        cfg, plan = self.cfg, self.plan
        gsz = cfg.group_size
        if layer_plans is None:
            layer_plans = plan.layers
        if kv_replicated is None:
            kv_replicated = plan.mode == "kv_replication"
        layers = params["layers"]
        is_stacked = not isinstance(layers, (list, tuple))

        def permute_layer(lp, layer_plan):
            ap = lp["attn"]
            wq, wk, wv, wo = permute_attention_params(
                np.asarray(ap["wq"]), np.asarray(ap["wk"]),
                np.asarray(ap["wv"]), np.asarray(ap["wo"]),
                layer_plan, cfg.head_dim_, gsz,
                kv_replicated=kv_replicated)
            new_ap = dict(ap, wq=jnp.asarray(wq), wk=jnp.asarray(wk),
                          wv=jnp.asarray(wv), wo=jnp.asarray(wo))
            return dict(lp, attn=new_ap)

        if is_stacked:
            stacked = layers
            new = []
            for l in range(cfg.num_layers):
                lp = jax.tree.map(lambda x: np.asarray(x[l]), stacked)
                new.append(permute_layer(lp, layer_plans[l]))
            layers_out = jax.tree.map(
                lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *new)
        else:
            layers_out = [permute_layer(lp, layer_plans[l])
                          for l, lp in enumerate(layers)]
        return dict(params, layers=layers_out)

    def worklists_for(self, seq_len: int) -> list[np.ndarray]:
        """Per-layer merged work-lists for a prefill of ``seq_len``.

        Single-host path: all shards' lists concatenated (head ids stay
        slot-local per device in the [D, L, 7] layout; for the 1-shard test
        engine D=1 so items address heads directly).

        Keyed by ``(epoch, PREFILL BUCKET)``, not the raw length: every
        caller pads its prompt to the bucket anyway, and raw-length keys
        would grow this cache unboundedly under varied traffic (pow2
        buckets bound it at O(log max_seq_len) entries per epoch; "exact"
        bucketing keeps the old one-entry-per-length behavior by
        definition).  Epoch swaps purge dead-epoch entries.
        """
        bucket = self._prefill_bucket(seq_len)
        key = (self.epoch, bucket)
        if key in self._worklists_cache:
            return self._worklists_cache[key]
        assert self.plan is not None
        pol = policy_by_name(self.ecfg.policy)
        out = []
        for l in range(self.cfg.num_layers):
            budgets = self.plan.layers[l].budgets  # slot order
            wl: WorkList = worklist_from_budgets(
                budgets,
                num_devices=self.ecfg.num_model_shards,
                seq_len=bucket,
                block=self.ecfg.block,
                policy_fn=pol,
                group_size=self.cfg.group_size,
            )
            out.append(wl)
        self._worklists_cache[key] = out
        return out

    def decode_block_ids(self, cache_len: int,
                         nb_pad: int | None = None) -> np.ndarray:
        """[L, Hkv, nb] decode budgets -> selected blocks (-1 pad).

        Per kv head: budget = max over its q heads (slot order); blocks =
        sink + most recent (streaming within budget; selection policy for
        decode can be swapped for quest scores at runtime).  ``nb_pad``
        fixes the trailing width (position-aware serving pads every
        selection to the max-budget width so shapes are step-invariant).
        """
        assert self.plan is not None
        cfg = self.cfg
        gsz = cfg.group_size
        nkv_blocks = -(-cache_len // self.ecfg.block)
        per_layer = []
        nb_max = 1
        for l in range(cfg.num_layers):
            budgets = self.plan.layers[l].budgets.reshape(
                cfg.num_kv_heads, gsz).max(axis=1)
            nb = np.minimum(blocks_for_budget(budgets, self.ecfg.block),
                            nkv_blocks)
            nb_max = max(nb_max, int(nb.max()))
            per_layer.append(nb)
        width = nb_max if nb_pad is None else nb_pad
        ids = np.full((cfg.num_layers, cfg.num_kv_heads, width), -1,
                      np.int32)
        for l, nb in enumerate(per_layer):
            for h in range(cfg.num_kv_heads):
                n = min(int(nb[h]), width)
                # the NEWEST block (holding the token just written) is
                # always selected — at n == 1 it wins over the sink, else
                # sink + the n-1 most recent.  (The old `[0] + recent(n-1)`
                # attended ONLY the sink at minimum budget, silently
                # dropping recency/causality.)
                recent = range(max(0, nkv_blocks - max(1, n - 1)), nkv_blocks)
                sel = sorted(set(([0] if n > 1 else []) + list(recent)))[:n]
                ids[l, h, :len(sel)] = sel
        return ids

    def _nb_cap_for_epoch(self) -> int:
        """Padded decode-selection width of the CURRENT epoch (a function
        of the epoch's budgets — recomputed once per epoch)."""
        cap = self._nb_cap.get(self.epoch)
        if cap is None:
            cap = self.decode_block_ids(self.ecfg.max_seq_len).shape[-1]
            self._nb_cap[self.epoch] = cap
        return cap

    def _decode_ids_for_nblocks(self, nblocks: int) -> np.ndarray:
        """Memoized position-aware selection for a slot holding ``nblocks``
        cache blocks — recomputed only when a slot crosses a block
        boundary (or the plan epoch changes), padded to the epoch's
        ``_nb_cap`` width."""
        cap_w = self._nb_cap_for_epoch()
        nblocks = max(1, min(nblocks,
                             self.ecfg.max_seq_len // self.ecfg.block))
        key = (self.epoch, nblocks)
        got = self._decode_ids_by_nblocks.get(key)
        if got is None:
            got = self.decode_block_ids(nblocks * self.ecfg.block,
                                        nb_pad=cap_w)
            self._decode_ids_by_nblocks[key] = got
            # the clamp above is the bound: one entry per possible resident
            # block count per LIVE epoch (dead epochs are purged at swap),
            # so host memory stays O(max_seq/block)
            assert len(self._decode_ids_by_nblocks) <= (
                self.ecfg.max_seq_len // self.ecfg.block), \
                "memoized decode-id table exceeded max_seq_len // block"
        return got

    # -- cost-packed ragged decode worklists (DESIGN.md §2.8) ---------------
    def _nb_sig(self, pos_all: np.ndarray) -> tuple[int, ...]:
        """Per-slot resident BLOCK COUNTS — the plan cache key.  Decode
        selections are a pure function of block counts (budgets are fixed
        per layer/head), so ticks between block boundaries share a plan."""
        blk = self.ecfg.block
        cap = self.ecfg.max_seq_len // blk
        return tuple(
            max(1, min(-(-(int(p) + 1) // blk), cap)) for p in pos_all)

    def _packed_item_cap(self) -> int:
        """Worst-case packed item count of one layer: every slot at the
        epoch's max-budget selection width, rounded up to the packer's pad
        multiple (pack_decode_items rounds shard lengths to 8, so an
        unrounded cap could fall below a near-full tick's padded length
        and make the bucket unable to hold it)."""
        cap = (self.ecfg.num_slots * self.cfg.num_kv_heads
               * self._nb_cap_for_epoch())
        return -(-cap // 8) * 8

    def _build_packed_plan(self, nb_sig: tuple[int, ...],
                           phys_of_block: np.ndarray | None = None):
        """Pack one tick's decode work: per layer, flatten every slot's
        position-aware selection into (row, kv_head, kv_block) items,
        best-partition the (row, head) runs across model shards, and pad
        all layers onto one pow2 item bucket.  ``phys_of_block`` ([B, T]
        logical->physical tables, prefix sharing §2.14) makes the packer
        charge a pool block's bytes ONCE per head worklist however many
        slots alias it.  Returns
        ``(items [L, D*bucket, DEC_FIELDS] int32, stats)``."""
        cfg, ecfg = self.cfg, self.ecfg
        per_slot = [self._decode_ids_for_nblocks(nb) for nb in nb_sig]
        bids = np.stack(per_slot, axis=1)       # [L, B, Hkv, nb_cap]
        wls = [pack_decode_items(bids[l], num_shards=ecfg.num_model_shards,
                                 block=ecfg.block,
                                 bytes_per_block=self._kv_block_bytes,
                                 phys_of_block=phys_of_block)
               for l in range(cfg.num_layers)]
        bucket = pow2_bucket(max(wl.padded_length for wl in wls),
                             lo=8, hi=self._packed_item_cap())
        items = np.stack([
            extend_packed_items(wl.items, bucket).reshape(-1, DEC_FIELDS)
            for wl in wls])                     # [L, D*bucket, DEC_FIELDS]
        real = sum(wl.total_real_items for wl in wls)
        grid = cfg.num_layers * ecfg.num_model_shards * bucket
        # the padded baseline's grid: every (slot, head) at the max-budget
        # selection width, every layer — one grid step per table entry
        padded_grid = int(bids.size)
        stats = {
            "epoch": self.epoch,
            "bucket": bucket,
            "real_items": real,
            "grid_items": grid,
            "padded_grid_items": padded_grid,
            "padding_waste": 1.0 - real / grid if grid else 0.0,
            "padded_path_waste": (1.0 - real / padded_grid
                                  if padded_grid else 0.0),
            "imbalance": float(np.mean([wl.imbalance for wl in wls])),
        }
        return items, stats

    def _stripe_of_table(self, table: np.ndarray) -> np.ndarray:
        """[B, T] owning seq stripe of each LOGICAL block position (-1 for
        unmapped) — stripe membership is a property of the PHYSICAL id."""
        ss = self.kv.stripe_size
        t = np.asarray(table)
        return np.where(t >= 0, t // ss, -1).astype(np.int32)

    def _build_packed_plan_2d(self, nb_sig: tuple[int, ...],
                              stripe_of: np.ndarray,
                              phys_of_block: np.ndarray | None = None):
        """2D twin of :meth:`_build_packed_plan` (DESIGN.md §2.11): each
        (slot, head) run splits into per-stripe sub-runs (stripe fixed by
        block placement), ``best_partition_2d`` picks model shards to
        minimize the max (shard, stripe) CELL, and every cell pads onto
        one pow2 bucket.  ``phys_of_block`` dedups shared-block bytes per
        (head, stripe) cell (§2.14).  Returns ``(items [L, S, Dm*bucket,
        DEC_FIELDS] int32, stats)`` — axis 1 is the stripe axis
        ``decode_step_paged`` loops over (one partial pass per stripe,
        merged)."""
        cfg, ecfg = self.cfg, self.ecfg
        S, Dm = ecfg.seq_shards, ecfg.num_model_shards
        per_slot = [self._decode_ids_for_nblocks(nb) for nb in nb_sig]
        bids = np.stack(per_slot, axis=1)       # [L, B, Hkv, nb_cap]
        wls = [pack_decode_items_2d(bids[l], stripe_of, num_stripes=S,
                                    num_shards=Dm, block=ecfg.block,
                                    bytes_per_block=self._kv_block_bytes,
                                    phys_of_block=phys_of_block)
               for l in range(cfg.num_layers)]
        bucket = pow2_bucket(max(wl.padded_length for wl in wls),
                             lo=8, hi=self._packed_item_cap())

        def flat(wl):
            # [Dm, S, Lp, F] -> per-cell pad to bucket -> [S, Dm*bucket, F]
            # (stripe-major: the executor's pass s consumes its Dm shards'
            # items as one flat single-host list)
            ext = extend_packed_items(
                wl.items.reshape(Dm * S, wl.padded_length, DEC_FIELDS),
                bucket)
            return np.swapaxes(
                ext.reshape(Dm, S, bucket, DEC_FIELDS), 0, 1
            ).reshape(S, Dm * bucket, DEC_FIELDS)

        items = np.stack([flat(wl) for wl in wls])
        real = sum(wl.total_real_items for wl in wls)
        grid = cfg.num_layers * Dm * S * bucket
        padded_grid = int(bids.size)
        stats = {
            "epoch": self.epoch,
            "bucket": bucket,
            "real_items": real,
            "grid_items": grid,
            "padded_grid_items": padded_grid,
            "padding_waste": 1.0 - real / grid if grid else 0.0,
            "padded_path_waste": (1.0 - real / padded_grid
                                  if padded_grid else 0.0),
            "imbalance": float(np.mean([wl.imbalance for wl in wls])),
            "model_imbalance": float(np.mean(
                [wl.model_imbalance for wl in wls])),
            "stripe_imbalance": float(np.mean(
                [wl.stripe_imbalance for wl in wls])),
        }
        return items, stats

    def _share_sig(self, table: np.ndarray | None):
        """Sharing signature of a tick's block tables (§2.14): per slot
        row, the (logical index, physical id) pairs of blocks referenced
        by MORE than one table.  Exactly these entries change the packer's
        charge-once weights (a refcount-1 block cannot appear twice), so
        keying plans on them — not the full tables — keeps the plan cache
        hitting across unrelated id churn.  None when sharing is off."""
        if table is None or self.prefix is None:
            return None
        tab = np.asarray(table)
        if self.kv.alloc.shared_block_count == 0:
            # nothing in the pool is multiply-referenced: every row's
            # signature is empty (identical to what the scan below would
            # build), so skip the per-slot x per-block refcount loop that
            # otherwise runs on every decode tick and prefetch
            return ((),) * tab.shape[0]
        rc = self.kv.alloc.refcount
        return tuple(
            tuple((i, b) for i, b in enumerate(row)
                  if b >= 0 and rc(b) >= 2)
            for row in tab.tolist())

    def _plan_key(self, nb_sig: tuple[int, ...],
                  stripe_of: np.ndarray | None,
                  share_sig=None) -> tuple:
        """Plan-cache key: (epoch, block counts[, stripe placement]
        [, sharing signature]) — the stripe signature makes a plan valid
        only for the exact physical placement it was packed against
        (swap/preempt cycles remap ids); the sharing signature does the
        same for the charge-once dedup weights."""
        key = ((self.epoch, nb_sig) if stripe_of is None
               else (self.epoch, nb_sig,
                     tuple(map(tuple, stripe_of.tolist()))))
        if share_sig is not None:
            key += (share_sig,)
        return key

    def _plan_for(self, nb_sig: tuple[int, ...],
                  stripe_of: np.ndarray | None = None,
                  prefetch: bool = False,
                  table: np.ndarray | None = None):
        """LRU-memoized packed plan for an ``(epoch, tick signature)`` —
        the epoch key means a replan can never serve a stale epoch's
        selections, while old-epoch plans age out of the LRU lazily.
        ``table`` (prefix sharing on) feeds the charge-once packing."""
        share_sig = self._share_sig(table)
        pob = table if share_sig is not None else None
        key = self._plan_key(nb_sig, stripe_of, share_sig)
        got = self._packed_plan_cache.get(key)
        if got is None:
            got = (self._build_packed_plan(nb_sig, phys_of_block=pob)
                   if stripe_of is None
                   else self._build_packed_plan_2d(nb_sig, stripe_of,
                                                   phys_of_block=pob))
            self._packed_plan_cache[key] = got
            if len(self._packed_plan_cache) > self._packed_plan_cap:
                self._packed_plan_cache.popitem(last=False)
            self.decode_stats["plan_prefetches" if prefetch
                              else "plan_misses"] += 1
        else:
            self._packed_plan_cache.move_to_end(key)
            if not prefetch:
                self.decode_stats["plan_hits"] += 1
        return got

    def _prefetch_next_plan(self) -> None:
        """Pipelined host planning: build the NEXT tick's packed worklist
        while the CURRENT tick's device step runs (jax dispatch is async —
        the block happens later, at sampling).  The scheduler's preview is
        best-effort; a mismatched prediction just means the real signature
        builds synchronously next tick (correctness is unaffected)."""
        if self._batcher is None:
            return
        preview = self._batcher.preview_next_decode()
        if not preview:
            return
        slots, positions = preview
        pos_all = np.zeros((self.ecfg.num_slots,), np.int32)
        pos_all[list(slots)] = positions
        sig = self._nb_sig(pos_all)
        stripe_of = table = None
        if self.ecfg.seq_shards > 1 or self.prefix is not None:
            # best-effort: if a slot maps a NEW block before the next tick
            # the stripe/sharing signature shifts and this plan simply
            # goes unused (the key carries the placement — never a wrong
            # plan)
            table = np.full((self.ecfg.num_slots, self.kv.table_width), -1,
                            np.int32)
            for s in slots:
                table[s] = self._table_for_slot(s)
            if self.ecfg.seq_shards > 1:
                stripe_of = self._stripe_of_table(table)
        key = self._plan_key(sig, stripe_of, self._share_sig(table))
        if key not in self._packed_plan_cache:
            self._plan_for(sig, stripe_of, prefetch=True, table=table)

    def _record_tick(self, stats: dict) -> None:
        s = self.decode_stats
        s["ticks"] += 1
        s["real_items"] += stats["real_items"]
        s["grid_items"] += stats["grid_items"]
        s["padded_grid_items"] += stats["padded_grid_items"]
        s["imbalance_sum"] += stats["imbalance"]
        # per-axis decomposition (§2.11): a 1D tick's whole imbalance is
        # head-axis by definition; striped ticks record both marginals
        s["head_imb_sum"] += stats.get("model_imbalance",
                                       stats["imbalance"])
        s["stripe_imb_sum"] += stats.get("stripe_imbalance", 1.0)
        s["last"] = stats
        self._epoch_stats[self.epoch]["ticks"] += 1

    @property
    def decode_bubble_stats(self) -> dict:
        """Aggregate decode-grid bubble telemetry: the fraction of executed
        grid steps that were padding, the same quantity the PADDED baseline
        would have paid, and their ratio (the packed win) — plus the
        plan-epoch aggregates (per-epoch realized recovery from the online
        estimator and the latest drift reading) — recorded by
        ``benchmarks/serving.py`` so the load-balance AND adaptivity gains
        are observable per run, not inferred."""
        s = self.decode_stats
        grid, real, padded = (s["grid_items"], s["real_items"],
                              s["padded_grid_items"])
        epochs = {}
        for e, es in self._epoch_stats.items():
            epochs[e] = {
                "ticks": es["ticks"],
                "telemetry_samples": es["telemetry_samples"],
                "realized_recovery": (es["recovery_sum"]
                                      / es["recovery_ticks"]
                                      if es["recovery_ticks"] else None),
                "drift": es["drift"],
            }
        return {
            "ticks": s["ticks"],
            "padding_waste": 1.0 - real / grid if grid else 0.0,
            "padded_path_waste": 1.0 - real / padded if padded else 0.0,
            "grid_vs_padded": grid / padded if padded else 1.0,
            "mean_imbalance": (s["imbalance_sum"] / s["ticks"]
                               if s["ticks"] else 1.0),
            # sequence-parallel long context (DESIGN.md §2.11): per-axis
            # imbalance marginals + the seq-merge collective count — makes
            # "which axis is the bottleneck" observable per run
            "seq_shards": self.ecfg.seq_shards,
            "mean_head_imbalance": (s["head_imb_sum"] / s["ticks"]
                                    if s["ticks"] else 1.0),
            "mean_stripe_imbalance": (s["stripe_imb_sum"] / s["ticks"]
                                      if s["ticks"] else 1.0),
            "merge_collectives": s["merge_collectives"],
            "plan_hits": s["plan_hits"],
            "plan_misses": s["plan_misses"],
            "plan_prefetches": s["plan_prefetches"],
            "last_tick": s["last"],
            "epoch": self.epoch,
            "replans": self.replans,
            "realized_recovery": (self.telemetry.realized_recovery()
                                  if self.telemetry is not None
                                  and self.telemetry.total_samples else None),
            "drift": self._last_drift[1] if self._last_drift else None,
            "epochs": epochs,
            # overload robustness (DESIGN.md §2.10): host-tier swap volume
            # and the scheduler's per-class admission/preemption counters
            "swap": dict(self.swap_stats),
            # fault tolerance (DESIGN.md §2.13): sentinel trips, swap
            # retry outcomes, audit passes, rollbacks — plus the injected
            # fault count so chaos runs can assert detection == injection
            "faults": dict(self.fault_stats),
            "injected_events": (len(self.injector.events)
                                if self.injector is not None else 0),
            "per_class": ({k: dict(v) for k, v in
                           self._batcher.stats.per_class.items()}
                          if self._batcher is not None else {}),
            # radix prefix cache (§2.14): hit/insert/evict counters plus
            # the live tree size and the evictable (cached, unreferenced)
            # block count — the cache's resident footprint under pressure
            "prefix": (dict(self.prefix.stats,
                            nodes=self.prefix.num_blocks,
                            evictable=self.kv.alloc.evictable_blocks)
                       if self.prefix is not None else None),
        }

    # -- plan epochs: telemetry, drift, replanning (DESIGN.md §2.9) ---------
    def _telemetry_fn(self, nb_width: int):
        """Un-donated jitted recovery probe, keyed by the selection-table
        width (epoch-dependent shape; the tables themselves are data)."""
        fn = self._telemetry_jit.get(nb_width)
        if fn is None:
            qz = self.quantized

            def telemetry_probe(params, cache, token, pos, table, bids,
                                clen):
                pool, scales = cache if qz else (cache, None)
                return tfm.decode_telemetry(
                    params, pool, token, pos, self.cfg,
                    block_ids=bids, cache_len=clen, table=table,
                    scales=scales, with_health=True)
            # reads the live cache: never donated
            fn = jax.jit(telemetry_probe)
            self._telemetry_jit[nb_width] = fn
        return fn

    def _dispatch_telemetry(self, slots, tok_all, pos_all, bids, table):
        """Dispatch the recovery probe against the PRE-STEP resident cache
        (before the donating decode step — stream order keeps the read
        safe) and return the pending (rec, frac, rows); the caller folds
        them in AFTER dispatching the decode step, so probe + step overlap
        host planning exactly like the packed-plan prefetch."""
        fn = self._telemetry_fn(bids.shape[-1])
        rec, frac, fin = fn(self.params, self.cache, jnp.asarray(tok_all),
                            jnp.asarray(pos_all), jnp.asarray(table),
                            jnp.asarray(bids), jnp.asarray(pos_all))
        return rec, frac, fin, list(slots)

    def _fold_telemetry(self, pending) -> None:
        rec, frac, fin, rows = pending
        fin = np.asarray(fin)
        if self.ecfg.sentinels:
            # deep sentinel (DESIGN.md §2.13): the probe's estimator
            # forward went non-finite for this row — quarantine it even if
            # its serving logits look clean this tick
            for r in rows:
                if not fin[r] and int(r) not in self._quarantine:
                    self._quarantine[int(r)] = "probe_nonfinite"
                    self.fault_stats["sentinel_trips"] += 1
        # a poisoned row's recovery estimates are NaN — fold only healthy
        # rows so one victim cannot corrupt the online estimator (and with
        # it every future replan)
        rows = [r for r in rows if fin[r]]
        if not rows:
            return
        rec = np.asarray(rec, np.float64)[:, rows, :]    # [L, B_act, H]
        frac = np.asarray(frac, np.float64)[:, rows, :]
        if not (np.isfinite(rec).all() and np.isfinite(frac).all()):
            rec = np.nan_to_num(rec, nan=0.0, posinf=1.0, neginf=0.0)
            frac = np.nan_to_num(frac, nan=0.0, posinf=1.0, neginf=0.0)
        # the probe runs on HPLB-permuted params, so head h above is SLOT
        # h (physical head perm[h]); the estimator, the drift reference
        # profiles, and the replanner all live in ORIGINAL head order —
        # scatter each layer back through the plan's perm so head
        # identities survive any epoch's placement (and EMAs stay pinned
        # to physical heads across swaps)
        rec_o = np.empty_like(rec)
        frac_o = np.empty_like(frac)
        for l, lp in enumerate(self.plan.layers):
            rec_o[l][:, lp.perm] = rec[l]
            frac_o[l][:, lp.perm] = frac[l]
        self.telemetry.update(rec_o, frac_o)
        es = self._epoch_stats[self.epoch]
        es["telemetry_samples"] += len(rows)
        es["recovery_sum"] += float(rec.mean())
        es["recovery_ticks"] += 1

    def _maybe_replan(self, batcher=None) -> bool:
        """Replan policy hook, called once per scheduler tick by
        :meth:`serve` (external tick loops may call it themselves).
        Swaps only at a safe point; returns True when an epoch swap
        happened."""
        ecfg = self.ecfg
        if self.plan is None or (ecfg.replan_every is None
                                 and ecfg.drift_threshold is None):
            return False
        batcher = batcher or self._batcher
        if batcher is not None and not batcher.replan_safe:
            return False
        due = (ecfg.replan_every is not None
               and self._ticks_since_replan >= ecfg.replan_every)
        if (not due and ecfg.drift_threshold is not None
                and self.telemetry.total_samples):
            # drift only moves when new samples were folded: memoize by
            # the sample count so non-probe ticks pay a dict lookup, not
            # a full curve refit
            n = (self.telemetry.total_samples, self.epoch)
            if self._last_drift is None or self._last_drift[0] != n:
                self._last_drift = (
                    n, self.telemetry.drift_vs(self._plan_profile))
            drift = self._last_drift[1]
            self._epoch_stats[self.epoch]["drift"] = drift["drift"]
            due = drift["drift"] >= ecfg.drift_threshold
        if not due:
            return False
        return self.replan_now()

    def replan_now(self, profile: HeadSparsityProfile | None = None, *,
                   plan: HPLBPlan | None = None) -> bool:
        """Re-derive budgets + head placement and swap the engine onto the
        new plan epoch IN FLIGHT (DESIGN.md §2.9).

        ``profile``: plan on this profile; default = the online
        estimator's live curves, falling back to the offline profile for
        unobserved heads.  The allocator warm-starts from the previous
        epoch's budgets (incremental max-min).  ``plan`` bypasses planning
        entirely and swaps onto an externally computed plan (a central
        planner service, or a test forcing a specific placement) — its
        geometry must match the engine's.  A no-op plan (same placement
        and budgets) bumps nothing and returns False.
        """
        assert self.plan is not None, "replan needs a sparse engine"
        self._ticks_since_replan = 0
        if plan is not None:
            new_plan = dataclasses.replace(plan, epoch=self.epoch + 1)
        else:
            if profile is None:
                profile = self.telemetry.to_profile(fallback=self.profile)
            ecfg = self.ecfg
            new_plan = make_plan(
                profile,
                num_devices=ecfg.num_model_shards,
                num_kv_heads=self.cfg.num_kv_heads,
                seq_len=ecfg.max_seq_len,
                total_budget_per_head=ecfg.budget_per_head,
                block=ecfg.block, floor=ecfg.floor,
                allocator=ecfg.allocator, partitioner=ecfg.partitioner,
                prev_plan=self.plan, epoch=self.epoch + 1)
        if plans_equal(self.plan, new_plan):
            log.info("replan@tick %d: plan unchanged (epoch stays %d)",
                     self._decode_ticks, self.epoch)
            return False
        try:
            self._apply_epoch(new_plan)
        except EpochSwapError as e:
            # rollback (DESIGN.md §2.13): the seam fires before any state
            # mutates, so the old epoch's params/cache/plan are intact —
            # keep serving on them and let the next policy trigger retry
            self.fault_stats["replan_rollbacks"] += 1
            log.warning("epoch swap failed (%s) — keeping epoch %d "
                        "serving", e, self.epoch)
            return False
        self.maybe_audit(boundary=True)
        if profile is not None:
            self._plan_profile = profile
        return True

    def _apply_epoch(self, new_plan: HPLBPlan) -> None:
        """Swap to ``new_plan``: re-permute params host-side via the
        composable delta, gather the resident cache's kv-head axis once
        on-device, bump the epoch, and purge dead-epoch planning
        artifacts.  Compiled steps are NOT dropped eagerly — the LRU memos
        retire them lazily; jits whose plan inputs are data (chunk
        prefill, decode) are epoch-invariant and keep serving.

        Commit discipline (DESIGN.md §2.13): the ``epoch_swap`` fault seam
        fires FIRST — before any mutation — and the re-permuted params are
        committed together with the plan/epoch at the end, so a failed
        swap raises :class:`EpochSwapError` with the old epoch fully
        intact and :meth:`replan_now` keeps serving on it."""
        inj = self.injector
        if inj is not None and inj.enabled:
            if inj.fire("epoch_swap") is not None:
                raise EpochSwapError(
                    "epoch_swap",
                    f"injected swap failure at epoch {self.epoch} -> "
                    f"{new_plan.epoch}")
        delta = plan_delta(self.plan, new_plan)
        new_params = self.params
        if not delta.identity:
            new_params = self._permute_params(
                self.params, layer_plans=delta.layers,
                kv_replicated=(delta.mode == "kv_replication"))
            kv_tbl = delta.kv_perm_table()
            if not np.array_equal(
                    kv_tbl, np.tile(np.arange(kv_tbl.shape[1], dtype=kv_tbl.dtype),
                                    (kv_tbl.shape[0], 1))):
                self._set_cache(self._kv_permute_fn()(
                    self.cache, jnp.asarray(kv_tbl)))
                # fold the gather into the cumulative arrangement: slot h
                # now holds what slot kv_tbl[l, h] held — swapped-out host
                # copies are NOT touched here; swap-in re-arranges them
                # against this record exactly once (DESIGN.md §2.10)
                self._kv_arrange = np.take_along_axis(
                    self._kv_arrange, np.asarray(kv_tbl), axis=1)
        old = self.epoch
        self.params = new_params
        self.plan = new_plan
        self.epoch = new_plan.epoch
        self.replans += 1
        self._epoch_stats[self.epoch] = self._fresh_epoch_stats()
        # purge the plain (unbounded) epoch-keyed dicts of dead epochs;
        # LRU-bounded memos (prefill jits, packed plans) evict lazily
        for d in (self._worklists_cache, self._chunk_cap,
                  self._chunk_wl_cache, self._decode_ids_by_nblocks):
            for k in [k for k in d if k[0] != self.epoch]:
                del d[k]
        self._nb_cap.pop(old, None)
        if self.prefix is not None:
            # cached prefix KV was computed under the OLD epoch's budgets
            # (and head placement) — a new-plan prefill would not reproduce
            # it bitwise, so the tree drops everything; unreferenced blocks
            # free, shared ones free as their holders finish (§2.14)
            self.prefix.flush()
        log.info("plan epoch %d -> %d at tick %d (moved=%s, "
                 "mean imbalance %.3f)", old, self.epoch,
                 self._decode_ticks, not delta.identity,
                 new_plan.mean_imbalance)

    def _kv_permute_fn(self):
        """Donated gather of the resident cache's kv-head axis for an
        epoch swap."""
        if self._kv_permute_jit is None:
            if self.quantized:
                # codes AND scales gather in ONE donated jit — a block's
                # scale can never go out of sync with its codes across an
                # epoch swap
                def kv_permute(cache, tbl):
                    pool, scales = cache
                    return (tfm.permute_cache_kv_heads(pool, tbl),
                            tfm.permute_cache_scales(scales, tbl))
            else:
                def kv_permute(cache, tbl):
                    return tfm.permute_cache_kv_heads(cache, tbl)
            self._kv_permute_jit = jax.jit(
                kv_permute, donate_argnums=(0,) if self._donate else ())
        return self._kv_permute_jit

    # -- pool plumbing -------------------------------------------------------
    def _set_cache(self, cache) -> None:
        """Adopt the buffer a jitted step returned; keep the PagedKVCache
        handle pointing at the live pool (and, quantized, the live
        scales — pool_bytes() charges both)."""
        self.cache = cache
        if self.quantized:
            self.kv.pool, self.kv.scales = cache
        else:
            self.kv.pool = cache

    def _table_for_slot(self, slot: int) -> np.ndarray:
        """[T] int32 pool block ids (-1 pad) of the sequence in ``slot``."""
        assert self._batcher is not None, \
            "paged engine steps need a batcher (make_batcher binds it)"
        return self.kv.table_row(self._batcher.rid_of_slot(slot))

    # -- preemption: KV block swap to pinned host (DESIGN.md §2.10) ----------
    # A preempted decode's mapped blocks are gathered to host in one
    # donated jit (the pool buffer passes through aliased — no copy of the
    # pool itself), the allocator migrates the accounting, and the ids are
    # immediately reusable.  Swap-in scatters the host copy into FRESHLY
    # mapped blocks (ids differ; identity is the block table, not the id).
    # Jits are keyed by the pow2 block bucket (ids pad with the trash
    # block / token-padding is junk beyond the resident length), so swap
    # compiles O(log table_width) programs total.

    def _swap_bucket(self, nblk: int) -> int:
        return pow2_bucket(nblk, lo=1,
                           hi=self.ecfg.max_seq_len // self.ecfg.block)

    def _swap_gather_fn(self, bucket: int):
        fn = self._swap_gather_jit.get(bucket)
        if fn is None:
            qz = self.quantized

            # quantized: the scales gather rides the SAME ids — the host
            # copy is (codes, scales), ~cache_dtype_bytes/2 the bf16 swap
            # volume per block
            def swap_gather(cache, ids):
                pool, scales = cache if qz else (cache, None)
                blocks = jnp.take(pool, ids, axis=2)
                if qz:
                    return (pool, scales), (
                        blocks, jnp.take(scales, ids, axis=2))
                return pool, blocks
            fn = jax.jit(swap_gather,
                         donate_argnums=(0,) if self._donate else ())
            self._swap_gather_jit[bucket] = fn
        return fn

    def _swap_scatter_fn(self, bucket: int):
        fn = self._swap_scatter_jit.get(bucket)
        if fn is None:
            qz = self.quantized

            def swap_scatter(cache, blocks, ids):
                if qz:
                    pool, scales = cache
                    blk, sc = blocks
                    return (pool.at[:, :, ids].set(blk.astype(pool.dtype)),
                            scales.at[:, :, ids].set(sc))
                return cache.at[:, :, ids].set(blocks.astype(cache.dtype))
            fn = jax.jit(swap_scatter,
                         donate_argnums=(0,) if self._donate else ())
            self._swap_scatter_jit[bucket] = fn
        return fn

    def _transfer_gate(self, seam: str, rid: int) -> None:
        """Bounded retry-with-backoff around a host<->device transfer
        (DESIGN.md §2.13).  The injector models the transfer attempt: each
        retry RE-FIRES the seam, so a fault spec with ``times <=
        swap_retries`` heals transparently and one with ``times >
        swap_retries`` exhausts the budget and raises
        :class:`TransferError` — which the scheduler turns into
        discard-and-requeue.  Fires BEFORE any device work each attempt,
        so the donated cache is never left half-transferred."""
        inj = self.injector
        if inj is None or not inj.enabled:
            return
        retries = self.ecfg.swap_retries
        for attempt in range(retries + 1):
            spec = inj.fire(seam, rid=rid)
            if spec is None:
                if attempt:
                    self.fault_stats["swap_recoveries"] += 1
                    log.info("%s rid=%d recovered on retry %d",
                             seam, rid, attempt)
                return
            if spec.mode == "delay":
                time.sleep(spec.value)
                return
            self.fault_stats["swap_faults"] += 1
            if attempt < retries:
                self.fault_stats["swap_retries"] += 1
                if self.ecfg.swap_backoff_s > 0:
                    time.sleep(self.ecfg.swap_backoff_s * (2 ** attempt))
        self.fault_stats["swap_giveups"] += 1
        raise TransferError(
            seam, f"transfer failed after {retries + 1} attempts", rid=rid)

    def _swap_out_seq(self, rid: int, slot: int, resident: int) -> None:
        """Batcher swap-out hook: copy the sequence's mapped pool blocks
        to host BEFORE the allocator recycles them."""
        self._transfer_gate("swap_out_transfer", rid)
        sdata = None
        # prefix sharing (§2.14): tree-cached / multiply-referenced prefix
        # blocks STAY RESIDENT (their payload serves every other holder
        # already) — only the private tail transfers
        retained, private = self.kv.alloc.swap_split(rid)
        assert len(retained) + len(private) == \
            self.kv.alloc.blocks_needed(resident)
        shared_n = len(retained)
        nblk = len(private)
        if nblk:
            bucket = self._swap_bucket(nblk)
            row = np.full((bucket,), self.kv.trash_block, np.int32)
            row[:nblk] = private
            pool, blocks = self._swap_gather_fn(bucket)(
                self.cache, jnp.asarray(row))
            self._set_cache(pool)
            if self.quantized:
                blocks, sc = blocks
                sdata = np.array(jax.device_get(sc)[:, :, :nblk])
            data = np.array(jax.device_get(blocks)[:, :, :nblk])
        else:
            # fully shared: zero transfer; keep an empty host payload so
            # swap-in's shape bookkeeping stays uniform
            pool0 = self.cache[0] if self.quantized else self.cache
            L, two, _, Hkv, blk, Dh = pool0.shape
            data = np.zeros((L, two, 0, Hkv, blk, Dh), pool0.dtype)
            if self.quantized:
                sdata = np.zeros((L, two, 0, Hkv), np.float32)
        self._host_swaps[rid] = {"data": data, "scales": sdata,
                                 "tokens": resident,
                                 "shared_blocks": shared_n,
                                 "arrange": self._kv_arrange.copy()}
        st = self.swap_stats
        st["swapped_out"] += 1
        st["blocks_out"] += nblk
        st["bytes_out"] += data.nbytes + (sdata.nbytes if sdata is not None
                                          else 0)

    def _swap_in_seq(self, rid: int, slot: int, resident: int) -> None:
        """Batcher swap-in hook: restore the host copy into the freshly
        mapped blocks.  If plan epochs re-permuted the resident cache's
        kv-head axis while the sequence was out, the host copy is
        re-arranged here — exactly once, against the cumulative
        arrangement, no matter how many epoch swaps passed (the §2.9 cache
        gather composed them)."""
        # gate BEFORE popping the host record: a failed (given-up) swap-in
        # leaves the copy intact for the scheduler's fallback to discard
        self._transfer_gate("swap_in_transfer", rid)
        rec = self._host_swaps.pop(rid)
        assert rec["tokens"] == resident, \
            f"swap-in length mismatch: {rec['tokens']} != {resident}"
        data = rec["data"]
        sdata = rec["scales"]
        if not np.array_equal(rec["arrange"], self._kv_arrange):
            # rel[l, h] = where (in the host copy) the kv head now wanted
            # at slot h was stored when the copy was taken
            inv = np.argsort(rec["arrange"], axis=1)
            rel = np.take_along_axis(inv, self._kv_arrange, axis=1)
            data = np.take_along_axis(
                data, rel[:, None, None, :, None, None], axis=3)
            if sdata is not None:
                # scales [L, 2, nblk, Hkv] have kv heads on axis 3 too
                sdata = np.take_along_axis(sdata, rel[:, None, None, :],
                                           axis=3)
            self.swap_stats["epoch_remaps"] += 1
        # alloc.swap_in re-mapped only the PRIVATE tail: the leading
        # shared_n table entries are the retained resident prefix (§2.14)
        # and never left the device, so the host copy scatters past them —
        # into the fresh ids only
        shared_n = rec.get("shared_blocks", 0)
        ids = self.kv.alloc.table(rid)[shared_n:]
        nblk = len(ids)
        assert nblk == data.shape[2], \
            f"swap-in block mismatch: {nblk} != {data.shape[2]}"
        if nblk:
            bucket = self._swap_bucket(nblk)
            row = np.full((bucket,), self.kv.trash_block, np.int32)
            row[:nblk] = ids
            L, two, _, Hkv, blk, Dh = data.shape
            buf = np.zeros((L, two, bucket, Hkv, blk, Dh), data.dtype)
            buf[:, :, :nblk] = data
            payload = jnp.asarray(buf)
            if self.quantized:
                sbuf = np.ones((L, two, bucket, Hkv), np.float32)
                sbuf[:, :, :nblk] = sdata
                payload = (payload, jnp.asarray(sbuf))
            pool = self._swap_scatter_fn(bucket)(
                self.cache, payload, jnp.asarray(row))
            self._set_cache(pool)
        st = self.swap_stats
        st["swapped_in"] += 1
        st["blocks_in"] += nblk
        st["bytes_in"] += data.nbytes + (sdata.nbytes if sdata is not None
                                         else 0)

    # -- self-healing: sentinels, quarantine, audits (DESIGN.md §2.13) -------
    def take_quarantine(self) -> dict[int, str]:
        """Drain the sentinel flags raised by the last step — the batcher's
        ``sentinel_fn``.  Returns ``{slot: fail_reason}`` and clears."""
        got, self._quarantine = self._quarantine, {}
        return got

    def _sentinel_check(self, logits, row_slots) -> None:
        """Flag any slot whose last-step logits went non-finite.
        ``row_slots``: (logits row, slot) pairs — decode rows ARE slots,
        a prefill's single row maps to the sequence's slot.  Sampling
        brings back only token ids, so the check copies the whole
        ``[rows, vocab]`` logits array to the host (one device-to-host
        transfer per step, after the step has finished) and reduces it
        with numpy."""
        if not self.ecfg.sentinels:
            return
        with TraceAnnotation("engine.sentinel"):
            finite = np.isfinite(np.atleast_2d(np.asarray(logits))).all(
                axis=-1)
            for row, s in row_slots:
                if not finite[row] and int(s) not in self._quarantine:
                    self._quarantine[int(s)] = "nonfinite_logits"
                    self.fault_stats["sentinel_trips"] += 1

    def _poison_gate(self, logits, slot: int):
        """``poison_request`` seam: an injected fault turns THIS prefill's
        logits into NaN — modelling a request whose inputs drive the
        network into garbage.  The sentinel below must catch it."""
        inj = self.injector
        if inj is None or not inj.enabled:
            return logits
        rid = None
        if self._batcher is not None:
            try:
                rid = self._batcher.rid_of_slot(slot)
            except KeyError:
                rid = None
        spec = inj.fire("poison_request", rid=rid)
        if spec is None:
            return logits
        # rid-scoped specs only poison their designated victim
        if spec.rid is not None and rid is not None and spec.rid != rid:
            return logits
        return jnp.full_like(logits, jnp.nan)

    def _maybe_corrupt(self, slots) -> None:
        """``kv_corrupt`` seam: before this tick's decode step, flip one
        victim's OLDEST resident KV block to NaN/Inf — VALUE-plane values
        for bf16 caches, VALUE-plane scales for quantized ones (one bad
        dequant scale poisons the whole block, the int8/fp8 failure
        mode this models).  The first block always holds attended prompt
        tokens, so the fault is observable THIS tick (the newest block
        can be freshly mapped and still masked).  The value plane
        specifically: a poisoned KEY turns the softmax normalizer ``l``
        non-finite and the masked-row finalize guard (``where(l > 0,
        acc/l, 0)`` — load-bearing for all-masked stripes) silently
        zeroes the row, whereas a poisoned VALUE keeps scores finite and
        rides the accumulator straight into the victim's logits, which
        is exactly the observability the sentinel contract needs.
        Without prefix sharing blocks are per-sequence, so only the
        victim goes non-finite; with the radix cache (§2.14) the victim's
        oldest block may be a SHARED prefix block — then every holder
        trips its sentinel, all of them quarantine, and the scheduler's
        fail path invalidates the tree node so the poisoned content can
        never seed another admission (the designed blast radius)."""
        inj = self.injector
        if inj is None or not inj.enabled:
            return
        spec = inj.fire("kv_corrupt")
        if spec is None:
            return
        slots = list(slots)
        if not slots:
            return
        victim = slots[0]
        if spec.rid is not None and self._batcher is not None:
            for s in slots:
                if self._batcher.rid_of_slot(s) == spec.rid:
                    victim = s
                    break
        bad = jnp.inf if spec.mode == "inf" else jnp.nan
        rid = (self._batcher.rid_of_slot(victim)
               if self._batcher is not None else None)
        ids = self.kv.alloc.table(rid) if rid is not None else []
        if not ids:
            return
        bid = int(ids[0])
        if self.quantized:
            pool, scales = self.cache
            self._set_cache((pool, scales.at[:, 1, bid].set(bad)))
        else:
            self._set_cache(self.cache.at[:, 1, bid].set(bad))
        self.fault_stats["corruptions_injected"] += 1
        log.warning("injected kv_corrupt (%s) into slot %d", spec.mode,
                    victim)

    def _release_seq(self, rid: int, slot: int | None) -> None:
        """Batcher ``on_fail_fn``: called for a quarantined (or discarded)
        sequence while its block table is still valid.  Drops any host
        copy and SCRUBS the sequence's device blocks (codes to zero,
        scales to one) — freed ids recycle into later admissions, and a
        kernel that multiplies instead of masking would propagate a stale
        NaN out of reused storage (NaN * 0 == NaN).

        Prefix sharing (§2.14): only blocks about to actually FREE are
        scrubbed — a block another sequence still references, or one the
        radix tree keeps as evictable content, must keep its payload.
        (The fault path invalidates the tree BEFORE this hook runs, so a
        quarantined sequence's corrupted blocks are uncached by now and
        scrub as soon as their last reference drops.)"""
        self._host_swaps.pop(rid, None)
        alloc = self.kv.alloc
        ids = [b for b in alloc.table(rid)
               if alloc.refcount(b) == 1 and not alloc.is_cached(b)]
        if not ids:
            return
        idx = jnp.asarray(np.asarray(ids, np.int32))
        if self.quantized:
            pool, scales = self.cache
            self._set_cache((
                pool.at[:, :, idx].set(jnp.zeros((), pool.dtype)),
                scales.at[:, :, idx].set(1.0)))
        else:
            self._set_cache(
                self.cache.at[:, :, idx].set(
                    jnp.zeros((), self.cache.dtype)))

    def audit(self, strict: bool = True) -> list[str]:
        """Engine-level invariant audit (DESIGN.md §2.13): the allocator's
        two-tier conservation / double-map / stripe-ownership checks, the
        device pool's shape agreement, and host-tier record agreement
        (every allocator-swapped sequence has exactly one host copy whose
        token count matches).  Returns the violations (empty = healthy);
        ``strict`` raises :class:`IntegrityError` on any."""
        fails = self.kv.audit(strict=False)
        alloc = self.kv.alloc
        swapped = set(alloc.swapped_seqs)
        held = set(self._host_swaps)
        for rid in sorted(swapped - held):
            fails.append(f"seq {rid} swapped-out in allocator but has no "
                         "host copy")
        for rid in sorted(held - swapped):
            fails.append(f"seq {rid} has a host copy but is not "
                         "swapped-out in the allocator")
        for rid in sorted(swapped & held):
            rec = self._host_swaps[rid]
            if alloc.host_tokens(rid) != rec["tokens"]:
                fails.append(
                    f"seq {rid} host tokens disagree: allocator "
                    f"{alloc.host_tokens(rid)} vs copy {rec['tokens']}")
            # prefix sharing (§2.14): the host payload must hold exactly
            # the PRIVATE tail — total blocks minus the retained resident
            # prefix both sides agree on
            shn = rec.get("shared_blocks", 0)
            if shn != alloc.host_shared_blocks(rid):
                fails.append(
                    f"seq {rid} retained-prefix disagree: allocator "
                    f"{alloc.host_shared_blocks(rid)} vs copy {shn}")
            want = alloc.blocks_needed(rec["tokens"]) - shn
            if rec["data"].shape[2] != want:
                fails.append(
                    f"seq {rid} host payload holds {rec['data'].shape[2]} "
                    f"blocks, expected {want}")
        if self.prefix is not None:
            tree_ids = self.prefix.block_ids()
            pinned = self.kv.alloc.cached_ids()
            if tree_ids != pinned:
                fails.append(
                    f"prefix tree / allocator pin drift: tree-only "
                    f"{sorted(tree_ids - pinned)}, alloc-only "
                    f"{sorted(pinned - tree_ids)}")
        if fails and strict:
            raise IntegrityError(fails)
        if not fails:
            self.fault_stats["audits"] += 1
        return fails

    def maybe_audit(self, boundary: bool = False) -> None:
        """Periodic audit hook: every ``audit_every`` decode ticks, plus
        forced at swap/replan ``boundary`` calls when auditing is on."""
        ae = self.ecfg.audit_every
        if ae <= 0:
            return
        if boundary or (self._decode_ticks and self._decode_ticks % ae == 0):
            self.audit(strict=True)

    def _maybe_checkpoint(self, batcher) -> None:
        """Checkpoint policy hook: every ``checkpoint_every`` decode ticks,
        at a replan-safe boundary (no prefill mid-flight — the same safe
        point epoch swaps use, so the snapshot is crash-consistent)."""
        ecfg = self.ecfg
        if (not ecfg.checkpoint_dir or ecfg.checkpoint_every <= 0
                or self._decode_ticks == 0
                or self._decode_ticks % ecfg.checkpoint_every != 0
                or not batcher.replan_safe):
            return
        from repro.serving import snapshot  # local: snapshot imports engine
        snapshot.save_serving(ecfg.checkpoint_dir, self, batcher)
        self.fault_stats["checkpoints"] += 1

    # -- jitted steps --------------------------------------------------------
    @staticmethod
    def _lru_get(cache: OrderedDict, key, build, cap: int):
        """OrderedDict LRU memo (the packed-plan cache's discipline,
        applied to the compiled-step memos): hit moves to the MRU end,
        miss builds and evicts the LRU entry past ``cap`` — so epoch swaps
        retire old-epoch programs bounded-lazily instead of leaking one
        compiled executable per (epoch, bucket) forever."""
        got = cache.get(key)
        if got is None:
            got = build()
            cache[key] = got
            if len(cache) > cap:
                cache.popitem(last=False)
        else:
            cache.move_to_end(key)
        return got

    def _prefill_bucket(self, seq_len: int) -> int:
        """Compile bucket for a prompt length: next power of two (floored
        at one block, capped at max_seq_len), or the exact length."""
        if self.ecfg.prefill_buckets != "pow2":
            return seq_len
        b = self.ecfg.block
        while b < seq_len:
            b *= 2
        return min(b, self.ecfg.max_seq_len)

    def _prefill_paged_fn(self, bucket: int):
        """Paged monolithic prefill for one compile bucket: the sequence
        cache is computed at the bucket length (not max_seq_len — the
        paged layout never materializes a max-length row) and lands in the
        pool with one block scatter through the table
        (``tfm.scatter_seq_cache_paged``).  The pool is donated; the table
        is data, so one compile serves every block placement and every
        real length within the bucket.  The epoch's work-lists are BAKED
        into the program (compile-time constants), so the memo key carries
        the epoch and the LRU cap retires old-epoch programs."""
        def build():
            blk = self.ecfg.block
            bucket_pad = -(-bucket // blk) * blk
            if self.ecfg.attention == "sparse":
                wls = self.worklists_for(bucket)
                items = [jnp.asarray(w.items.reshape(-1, w.items.shape[-1]))
                         for w in wls]
            else:
                items = None

            qz = self.quantized

            def prefill_paged(params, pool, tokens, table, last_idx):
                logits, seq_cache = tfm.prefill(
                    params, tokens, self.cfg, cache_len=bucket_pad,
                    sparse_items=items, last_index=last_idx)
                if qz:
                    p, scales = pool
                    p, scales = tfm.scatter_seq_cache_paged(
                        p, seq_cache, table, scales=scales,
                        kv_dtype=self.ecfg.kv_dtype)
                    return logits, (p, scales)
                pool = tfm.scatter_seq_cache_paged(pool, seq_cache, table)
                return logits, pool

            return jax.jit(prefill_paged,
                           donate_argnums=(1,) if self._donate else ())

        return self._lru_get(self._prefill_jit, (self.epoch, bucket),
                             build, self.ecfg.prefill_jit_cap)

    def _chunk_bucket(self, chunk_len: int, q_offset: int) -> int:
        """Compile bucket for one prefill chunk: next power of two (floored
        at one block), capped at the table rows LEFT after ``q_offset`` —
        an uncapped bucket would make the chunk's table dynamic_slice clamp
        its start index and silently overwrite earlier blocks.  The cap is
        a block multiple (max_seq_len and q_offset both are), so the bucket
        always spans whole q-blocks for the work-list slicing."""
        b = self.ecfg.block
        while b < chunk_len:
            b *= 2
        room = self.ecfg.max_seq_len - q_offset
        assert chunk_len <= room, "chunk overruns the sequence's table"
        return min(b, room)

    def _chunk_item_cap(self, nqc: int) -> int:
        """Fixed item-array width for a chunk of ``nqc`` q blocks: the max
        work-list items any nqc-block q-window can hold at max_seq_len
        (selection counts per q block depend only on the block index and
        the head budget, so this bounds every prompt bucket — per plan
        epoch, since budgets move at a replan)."""
        got = self._chunk_cap.get((self.epoch, nqc))
        if got is not None:
            return got
        wls = self.worklists_for(self._prefill_bucket(self.ecfg.max_seq_len))
        nmax = self.ecfg.max_seq_len // self.ecfg.block
        cap = 1
        for wl in wls:
            counts = chunk_item_counts(wl.items, nmax)
            win = np.convolve(counts, np.ones(min(nqc, nmax), np.int64),
                              mode="valid")
            cap = max(cap, int(win.max()))
        cap = -(-cap // 8) * 8  # friendly multiple
        self._chunk_cap[(self.epoch, nqc)] = cap
        return cap

    def _chunk_worklists(self, prompt_len: int, q_offset: int,
                         bucket: int) -> np.ndarray:
        """[L, P, 7] chunk work-lists: the monolithic prompt-bucket lists
        sliced to this chunk's q-block window (selections are EXACTLY the
        ones monolithic prefill would run, so chunked == monolithic
        token-for-token under greedy sampling).  Memoized — the slice
        depends only on (prompt bucket, offset, bucket), and re-filtering
        every layer's full list sits on the serving hot path."""
        assert bucket % self.ecfg.block == 0, "chunk bucket spans q-blocks"
        assert q_offset % self.ecfg.block == 0, "chunk offsets block-aligned"
        pbucket = self._prefill_bucket(prompt_len)
        nqc = bucket // self.ecfg.block
        ob = q_offset // self.ecfg.block
        key = (self.epoch, pbucket, ob, nqc)
        got = self._chunk_wl_cache.get(key)
        if got is None:
            cap = self._chunk_item_cap(nqc)
            full = self.worklists_for(pbucket)
            got = np.stack([
                chunk_items(wl.items, ob, nqc, pad_to=cap) for wl in full])
            self._chunk_wl_cache[key] = got
        return got

    def _prefill_chunk_fn(self, bucket: int):
        """Jitted chunked-prefill step for one chunk compile bucket, named
        ``prefill_chunk_b<bucket>`` (``prefill_chunk_dense_b<bucket>``).

        The chunk scatters straight into the sequence's pool blocks via the
        table; the pool is donated.  ``q_offset`` / ``kv_len`` /
        ``last_idx`` are traced scalars and the table and sparse work-lists
        enter as data, so one compile serves every sequence, offset,
        selection — and every plan EPOCH (no epoch in the key; the memo is
        LRU-bounded anyway so bucket churn cannot leak compiled entries).
        Quantized: each chunk's K/V quantize AT THE SCATTER (chunks are
        block-aligned so every block is quantized exactly once), and later
        chunks attend the earlier blocks through the dequant-fused
        worklist path."""
        def build():
            sparse = self.ecfg.attention == "sparse"
            qz = self.quantized
            kvd = self.ecfg.kv_dtype

            def step(params, cache, tokens, table, off, kv_len, last_idx,
                     items):
                pool, scales = cache if qz else (cache, None)
                out = tfm.prefill_chunk_paged(
                    params, pool, tokens, table, off, self.cfg,
                    kv_len=kv_len, sparse_items=items, last_index=last_idx,
                    scales=scales, kv_dtype=kvd)
                if qz:
                    return out[0], (out[1], out[2])
                return out

            def prefill_chunk(params, pool, tokens, table, off, kv_len,
                              last_idx, items):
                return step(params, pool, tokens, table, off, kv_len,
                            last_idx, items)

            def prefill_chunk_dense(params, pool, tokens, table, off,
                                    kv_len, last_idx):
                return step(params, pool, tokens, table, off, kv_len,
                            last_idx, None)

            # a name per bucket, so a trace tells the buckets apart
            fn = prefill_chunk if sparse else prefill_chunk_dense
            fn.__name__ = fn.__qualname__ = f"{fn.__name__}_b{bucket}"
            return jax.jit(fn, donate_argnums=(1,) if self._donate else ())

        return self._lru_get(self._prefill_chunk_jit, bucket, build,
                             self.ecfg.chunk_jit_cap)

    def _decode_fn(self):
        """Jitted decode step.  Sparse block ids enter as DATA ([L, B, Hkv,
        nb] per-slot selections) so position-aware re-selection at block
        boundaries never recompiles; the cache is donated."""
        if self._decode_jit is None:
            sparse = self.ecfg.attention == "sparse"
            qz = self.quantized
            kvd = self.ecfg.kv_dtype
            S = self.ecfg.seq_shards
            ss = self.kv.stripe_size if S > 1 else None

            def step(params, cache, token, pos, table, bids, act):
                pool, scales = cache if qz else (cache, None)
                out = tfm.decode_step_paged(
                    params, pool, token, pos, table, self.cfg,
                    block_ids=bids, cache_len=pos + 1, active=act,
                    seq_stripes=S, stripe_size=ss, scales=scales,
                    kv_dtype=kvd)
                if qz:
                    return out[0], (out[1], out[2])
                return out

            def decode_padded(params, pool, token, pos, table, bids, act):
                return step(params, pool, token, pos, table, bids, act)

            def decode_dense(params, pool, token, pos, table, act):
                return step(params, pool, token, pos, table, None, act)

            donate = (1,) if self._donate else ()
            self._decode_jit = (
                jax.jit(decode_padded, donate_argnums=donate) if sparse
                else jax.jit(decode_dense, donate_argnums=donate))
        return self._decode_jit

    def _decode_packed_fn(self, flat_len):
        """Jitted packed decode step for one item-bucket length.  The item
        table is DATA ([L, flat_len, DEC_FIELDS], or [L, S, flat_len,
        DEC_FIELDS] under striping — the key is then the (S, flat_len)
        shape pair) so plan changes within a bucket never recompile;
        distinct buckets compile once each (O(log worst-case) total — the
        prefill-bucket policy applied to grid lengths).  The cache is
        donated."""
        fn = self._decode_packed_jit.get(flat_len)
        if fn is None:
            qz = self.quantized
            kvd = self.ecfg.kv_dtype
            S = self.ecfg.seq_shards
            ss = self.kv.stripe_size if S > 1 else None

            def decode_packed(params, cache, token, pos, table, items, act):
                pool, scales = cache if qz else (cache, None)
                out = tfm.decode_step_paged(
                    params, pool, token, pos, table, self.cfg,
                    packed_items=items, cache_len=pos + 1, active=act,
                    seq_stripes=S, stripe_size=ss, scales=scales,
                    kv_dtype=kvd)
                if qz:
                    return out[0], (out[1], out[2])
                return out
            fn = jax.jit(decode_packed,
                         donate_argnums=(1,) if self._donate else ())
            self._decode_packed_jit[flat_len] = fn
        return fn

    # -- public API -----------------------------------------------------------
    def prefill_into_slot(self, tokens: np.ndarray, slot: int,
                          sampling: SamplingParams = SamplingParams()) -> int:
        """Prefill one sequence into its pool blocks; returns the first
        sampled token."""
        with TraceAnnotation("engine.prefill.inputs"):
            tokens = np.atleast_2d(np.asarray(tokens, np.int32))
            S = tokens.shape[-1]
            bucket = self._prefill_bucket(S)
            if bucket > S:
                tokens = np.pad(tokens, ((0, 0), (0, bucket - S)))
            table = self._table_for_slot(slot)
        with TraceAnnotation("engine.prefill.dispatch"):
            run = self._prefill_paged_fn(bucket)
            logits, cache = run(self.params, self.cache, jnp.asarray(tokens),
                                jnp.asarray(table), S - 1)
            self._set_cache(cache)
        return self._sample_first(logits, slot, sampling)

    def prefill_chunk_into_slot(self, tokens: np.ndarray, slot: int,
                                q_offset: int, prompt_len: int,
                                sampling: SamplingParams = SamplingParams(),
                                is_final: bool = True) -> int | None:
        """Prefill one chunk of a sequence into its pool blocks.

        ``tokens``: the chunk's real tokens [c]; ``q_offset``: tokens of
        this sequence already resident in the pool (block-aligned — the
        scheduler only emits block-aligned non-final chunks).  Returns the
        first sampled token when ``is_final`` (logits read at the chunk's
        last real row), else None.
        """
        sparse = self.ecfg.attention == "sparse"
        with TraceAnnotation("engine.prefill.inputs"):
            tokens = np.asarray(tokens, np.int32)
            c = tokens.shape[-1]
            bucket = self._chunk_bucket(c, q_offset)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :c] = tokens
            data = ([self._chunk_worklists(prompt_len, q_offset, bucket)]
                    if sparse else [])
            table = self._table_for_slot(slot)
        with TraceAnnotation("engine.prefill.dispatch"):
            run = self._prefill_chunk_fn(bucket)
            data = [jnp.asarray(d) for d in data]
            # chunks scatter straight into the sequence's pool blocks, and
            # decode never observes a mid-prefill sequence because its
            # blocks are disjoint
            logits, cache = run(self.params, self.cache, jnp.asarray(toks),
                                jnp.asarray(table), q_offset, q_offset + c,
                                c - 1, *data)
            self._set_cache(cache)
        if not is_final:
            return None
        return self._sample_first(logits, slot, sampling)

    def _sample_first(self, logits, slot: int,
                      sampling: SamplingParams) -> int:
        """A prefill's first token: the ``poison_request`` seam, sampling,
        the wait for the token, and the sentinel over its logits."""
        logits = self._poison_gate(logits, slot)
        with TraceAnnotation("engine.prefill.wait"):
            self._rng, sub = jax.random.split(self._rng)
            tok = int(sample(logits, sub, sampling)[0])
        self._sentinel_check(logits, [(0, slot)])
        return tok

    def decode_slots(self, slots, tokens, positions,
                     sampling: SamplingParams = SamplingParams()):
        """Advance all slots one step; returns sampled tokens for `slots`.

        Each host phase of the step is a profiler span
        (``engine.decode.*``, ``engine.sentinel``), so a device trace
        shows which one the chip waits on between two decode programs."""
        with TraceAnnotation("engine.decode.inputs"):
            tok_all = np.zeros((self.ecfg.num_slots,), np.int32)
            pos_all = np.zeros((self.ecfg.num_slots,), np.int32)
            act_all = np.zeros((self.ecfg.num_slots,), bool)
            tok_all[list(slots)] = tokens
            pos_all[list(slots)] = positions
            act_all[list(slots)] = True  # padded slots must not write KV
            self._decode_ticks += 1
            self._ticks_since_replan += 1
            # per-slot block tables (data): -1 rows for unbound slots route
            # their writes into the trash block
            table = np.full((self.ecfg.num_slots, self.kv.table_width), -1,
                            np.int32)
            for s in slots:
                table[s] = self._table_for_slot(s)
            # kv_corrupt seam fires BEFORE the probe dispatch and the step,
            # so both observe the corrupted block — detection is the test
            self._maybe_corrupt(slots)
            sparse = self.ecfg.attention == "sparse"
            packed = sparse and self.ecfg.decode_worklist == "packed"
            probe = (sparse and self.ecfg.telemetry_every > 0
                     and self._decode_ticks % self.ecfg.telemetry_every == 0)
            striped = self.ecfg.seq_shards > 1
        pending_probe = None
        if probe:
            # online recovery telemetry (DESIGN.md §2.9): probe the
            # PRE-STEP resident cache with this tick's selections — the
            # probe is dispatched before the donating decode step, so
            # stream order guarantees it reads the live buffer
            with TraceAnnotation("engine.decode.probe"):
                pending_probe = self._dispatch_telemetry(
                    slots, tok_all, pos_all, self._slot_block_ids(pos_all),
                    table=table)
        data = []
        if sparse:
            with TraceAnnotation("engine.decode.plan"):
                if packed:
                    # cost-packed ragged worklist: grid length is this
                    # tick's true selected-block count (bucketed), not
                    # B x Hkv x max-budget
                    stripe_of = (self._stripe_of_table(table) if striped
                                 else None)
                    items, stats = self._plan_for(self._nb_sig(pos_all),
                                                  stripe_of, table=table)
                    data = [items]
                else:
                    # padded baseline: per-slot position-aware selection,
                    # refreshed at block boundaries
                    data = [self._slot_block_ids(pos_all)]
                    stats = self._padded_tick_stats(data[0])
        with TraceAnnotation("engine.decode.dispatch"):
            if packed:
                shape = data[0].shape
                run = self._decode_packed_fn(shape[1:3] if striped
                                             else shape[1])
            else:
                run = self._decode_fn()
            args = [tok_all, pos_all, table] + data + [act_all]
            logits, cache = run(self.params, self.cache,
                                *map(jnp.asarray, args))
            self._set_cache(cache)
            if sparse:
                self._record_tick(stats)
            if striped:
                # one flash-decoding (out, m, l) combine per layer — on the
                # 2D mesh this is the single collective along the seq axis
                self.decode_stats["merge_collectives"] += self.cfg.num_layers
        if packed:
            # the device step above is dispatched asynchronously; build the
            # NEXT tick's plan now, before sampling forces a sync — host
            # planning overlaps the in-flight device work
            with TraceAnnotation("engine.decode.prefetch"):
                self._prefetch_next_plan()
        if pending_probe is not None:
            with TraceAnnotation("engine.decode.probe"):
                self._fold_telemetry(pending_probe)
        with TraceAnnotation("engine.decode.sample"):
            self._rng, sub = jax.random.split(self._rng)
            toks = sample(logits, sub, sampling)
        with TraceAnnotation("engine.decode.wait"):
            out = np.asarray(toks)[list(slots)]
        self._sentinel_check(logits, [(s, s) for s in slots])
        return out

    def _slot_block_ids(self, pos_all: np.ndarray) -> np.ndarray:
        """``[L, B, Hkv, nb_cap]`` block selections of every slot at its
        position (ids are a function of the block count)."""
        blk = self.ecfg.block
        return np.stack([self._decode_ids_for_nblocks(
            (int(p) + 1 + blk - 1) // blk) for p in pos_all], axis=1)

    def _padded_tick_stats(self, bids: np.ndarray) -> dict:
        """Bubble telemetry of a PADDED-path tick: real vs padded grid
        steps, and the (slot, head) run imbalance the packing removes."""
        real = int((bids >= 0).sum())
        grid = int(bids.size)
        counts = (bids >= 0).sum(axis=-1).astype(np.float64)  # [L, B, Hkv]
        mean = counts.mean() if counts.size else 0.0
        return {
            "epoch": self.epoch,
            "bucket": int(bids.shape[-1]),
            "real_items": real,
            "grid_items": grid,
            "padded_grid_items": grid,
            "padding_waste": 1.0 - real / grid if grid else 0.0,
            "padded_path_waste": 1.0 - real / grid if grid else 0.0,
            "imbalance": float(counts.max() / mean) if mean > 0 else 1.0,
        }

    def make_batcher(self, classes=None) -> ContinuousBatcher:
        """A ContinuousBatcher sized for this engine (chunked mixed ticks
        when ``prefill_mode == "chunked"``, else monolithic).

        The batcher SHARES the PagedKVCache's allocator, so admission
        control and the device pool count the very same blocks — a
        request is admitted when its blocks fit, and ``num_slots`` only
        bounds the decode batch width.  ``classes`` overrides the
        :data:`~repro.serving.scheduler.DEFAULT_CLASSES` table (the
        overload benchmark scales SLO targets to the measured tick time).
        """
        from repro.serving.scheduler import DEFAULT_CLASSES
        chunked = self.ecfg.prefill_mode == "chunked"
        if self.ecfg.prefix_cache and self.prefix is None:
            # the tree OUTLIVES individual batchers (serve() builds one
            # per call; restores rebuild one) so cached prefixes stay
            # warm; _grow drains it under pool pressure via evict_fn —
            # eviction absorbs pressure BEFORE preemption (§2.14)
            self.prefix = RadixPrefixCache(self.kv.alloc, self.ecfg.block)
            self.kv.alloc.evict_fn = self.prefix.evict
        b = ContinuousBatcher(
            num_slots=self.ecfg.num_slots,
            num_blocks=self.kv.num_blocks,
            max_seq_len=self.ecfg.max_seq_len,
            block=self.ecfg.block,
            token_budget=self.ecfg.prefill_chunk_tokens if chunked else None,
            allocator=self.kv.alloc,
            classes=classes if classes is not None else DEFAULT_CLASSES,
            admission=self.ecfg.admission,
            preemption=self.ecfg.preemption,
            host_blocks=self.ecfg.host_swap_blocks,
            swap_out_fn=self._swap_out_seq if self.ecfg.preemption else None,
            swap_in_fn=self._swap_in_seq if self.ecfg.preemption else None,
            sentinel_fn=self.take_quarantine,
            on_fail_fn=self._release_seq,
            prefix_cache=self.prefix)
        self._batcher = b
        return b

    def step_fns(self, sampling: SamplingParams = SamplingParams()):
        """(prefill_chunk_fn, decode_fn) closures for a ContinuousBatcher."""
        def prefill_chunk(toks, slot, q_offset, is_final, prompt_len):
            if self.ecfg.prefill_mode == "monolithic" and not q_offset:
                # whole prompt in one chunk: the prompt-bucketed hot path
                return self.prefill_into_slot(toks[0], slot, sampling)
            if self.ecfg.prefill_mode == "monolithic":
                # prefix hit (§2.14): q_offset tokens are already resident
                # in shared blocks — monolithic prefill would rewrite them
                # (and redo their flops), so the tail runs as ONE final
                # chunk; its work-lists are sliced from the monolithic
                # plan, keeping greedy tokens bitwise identical
                return self.prefill_chunk_into_slot(
                    toks[0], slot, q_offset, prompt_len, sampling,
                    is_final=True)
            return self.prefill_chunk_into_slot(
                toks[0], slot, q_offset, prompt_len, sampling,
                is_final=is_final)

        def decode(slots, toks, pos):
            return self.decode_slots(slots, toks, pos, sampling)

        return prefill_chunk, decode

    def serve(self, prompts: list[np.ndarray],
              sampling: SamplingParams = SamplingParams(),
              priorities: list[str] | None = None) -> list[Request]:
        """Continuous-batching serve of a list of prompts.

        Returns ONE Request per submitted prompt, in rid (= input) order:
        completed requests carry their generated tokens; over-length
        requests come back with ``rejected=True`` and no tokens, so zipping
        results with inputs never misaligns.  ``priorities`` optionally
        names each prompt's :class:`PriorityClass` (default "standard").

        When a replan policy is configured (``replan_every`` /
        ``drift_threshold``) the loop checks it once per tick, at the
        tick boundary — the scheduler's safe point gating lives inside
        :meth:`_maybe_replan`.
        """
        batcher = self.make_batcher()
        for i, pr in enumerate(prompts):
            batcher.submit(Request(
                rid=i, prompt=np.asarray(pr, np.int32), sampling=sampling,
                priority=priorities[i] if priorities else "standard"))
        done = batcher.run(*self.step_fns(sampling),
                           on_tick=lambda: self.on_tick(batcher))
        log.info("served %d requests: %s", len(done), batcher.stats)
        return sorted(done, key=lambda r: r.rid)

    def on_tick(self, batcher) -> None:
        """Per-tick policy hook (:meth:`serve` wires it; external loops
        can too): replan policy, invariant audits (periodic + forced at
        swap boundaries), and checkpointing at safe points."""
        with TraceAnnotation("engine.on_tick"):
            self._maybe_replan(batcher)
            if self.ecfg.audit_every > 0:
                activity = (self.swap_stats["swapped_out"]
                            + self.swap_stats["swapped_in"] + self.replans)
                boundary = activity != self._last_audit_activity
                self._last_audit_activity = activity
                self.maybe_audit(boundary=boundary)
            self._maybe_checkpoint(batcher)
