"""Batched serving engine: continuous-batching prefill + decode.

A slot-based engine in the vLLM mold, adapted to what ZO fine-tuning
produces (a model whose checkpoints are tiny seed-chains — see
checkpoint/manager.py):

  * fixed number of SLOTS (the decode batch); each slot holds one request's
    generation state;
  * ``submit`` queues requests; ``step`` runs one decode for every live slot
    (one jitted serve_step, all slots in lockstep);
  * greedy or temperature sampling; EOS or max-token termination frees the
    slot for the next queued request.

KV layout is PAGED for cache families with absolute-position rows
(dense/moe, sliding_window=0): KV lives in fixed-size token blocks owned by
a refcounted ``KVBlockPool`` (serve/paged.py), each slot holds a block
table, and a per-adapter-scoped ``RadixCache`` lets a request whose prompt
extends an already-served prefix prefill only the suffix.  Prefill is
CHUNKED and BATCHED: one admission wave's uncached suffixes are packed into
length-bucketed groups (pad widths derived from the prompt limit, powers of
two — no hard-coded width) and each group runs as ONE jitted
``chunk_prefill`` call resuming from the gathered prefix KV.  Decode
assembles each slot's logical cache row from its block table (XLA gather by
default; the ``kernels/paged`` pallas kernel under REPRO_BACKEND=pallas),
feeds the UNCHANGED registry decode, and scatters the newly written row back
into the pool.  The contract is token-identity: output ids with the prefix
cache on equal output ids with it off (test_serve_paged.py).

SWA/ring caches and recurrent families keep the legacy per-slot dense path
(their cache rows are not absolute-position addressed), with the same
bucket-derived prefill widths.

Family dispatch (cache / recurrent state / cross-attention) reuses
models.registry's prefill/decode fns.

Multi-tenant serving (``repro.serve.tenants``): ``register_adapter`` hands
the engine a named changed-leaf delta over the frozen base, requests carry an
``adapter`` name, and each slot remembers which adapter it decodes with.  One
decode step batches heterogeneous adapters:

  * selection-sized deltas on dense/moe families take the STACKED path — the
    varying leaves are stacked along a slot axis and one ``jax.vmap`` over
    slots decodes every adapter in a single call (base leaves broadcast,
    never duplicated);
  * full-tree deltas (or recurrent families) fall back to GROUPED decode —
    one call per distinct adapter, merging only that group's slot rows
    (cache/state axis 1) into the step result.

Requests with no adapter and engines with no registered adapters take the
original single-model path unchanged.  Prefix-cache scoping follows adapter
identity: each adapter name roots its own radix subtree, so KV computed
under one tenant's delta is never served as another's (or the base's).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import bundle as make_bundle
from repro.models.config import ModelConfig
from repro.serve.paged import (KVBlockPool, RadixCache, bucket_for,
                               pow2ceil, prefill_buckets)


@dataclasses.dataclass
class Request:
    rid: int
    prompt_ids: list
    max_new_tokens: int = 16
    temperature: float = 0.0
    adapter: Optional[str] = None           # registered adapter name, or base
    times: dict = dataclasses.field(default_factory=dict)  # lifecycle stamps
    out_ids: list = dataclasses.field(default_factory=list)
    done: bool = False


@functools.partial(jax.jit, static_argnames=("cfg",))
def _chunk_prefill(cfg, params, tokens, ck, cv, cpos, plens):
    """Module-level jit so the compile cache is keyed on (cfg, shapes) and
    shared by every engine in the process — a second engine (or a second
    traffic wave) over the same config re-uses the bucket's executable
    instead of re-compiling per engine instance."""
    fn = make_bundle(cfg).chunk_prefill_fn()
    return fn(params, {"tokens": tokens,
                       "cache": {"k": ck, "v": cv, "pos": cpos},
                       "cache_pos": plens})


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, slots: int = 4,
                 max_len: int = 256, eos_id: Optional[int] = None,
                 seed: int = 0, block: int = 16,
                 pool_blocks: Optional[int] = None, prefix_cache: bool = True,
                 paged: Optional[bool] = None, gather_impl: str = "auto"):
        assert cfg.family in ("dense", "moe", "hybrid", "ssm"), cfg.family
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.bundle = make_bundle(cfg)
        self.key = jax.random.PRNGKey(seed)

        paged_ok = cfg.family in ("dense", "moe") and cfg.sliding_window == 0
        self.paged = paged_ok if paged is None else bool(paged)
        if self.paged and not paged_ok:
            raise ValueError(
                f"paged KV requires absolute-position cache rows; family="
                f"{cfg.family!r} sliding_window={cfg.sliding_window} keeps "
                "the legacy dense-slab path (pass paged=None/False)")

        from repro.models import attention as attn_lib
        from repro.models import ssm as ssm_lib
        from repro.models import rwkv6 as rwkv_lib
        self.pool = self.radix = None
        if self.paged:
            self.cache = None                  # assembled per decode step
            self.block = block
            self._nblk_slot = -(-max_len // block)
            if pool_blocks is None:
                pool_blocks = 1 + 2 * slots * self._nblk_slot
            self.pool = KVBlockPool(cfg, pool_blocks, block, cfg.param_dtype)
            self.radix = RadixCache(self.pool) if prefix_cache else None
            self.tables: list[list] = [[] for _ in range(slots)]
            impl = gather_impl
            if impl == "auto":
                impl = os.environ.get("REPRO_BACKEND", "xla")
            self._gather_pallas = impl == "pallas"
        elif cfg.family != "ssm":
            self.cache = attn_lib.init_cache(cfg, slots, max_len,
                                             cfg.param_dtype, per_slot=True)
        else:
            self.cache = None
        if cfg.family == "hybrid":
            self.state = ssm_lib.init_ssm_state(cfg, slots)
        elif cfg.family == "ssm":
            self.state = rwkv_lib.init_rwkv_state(cfg, slots)
        else:
            self.state = None

        self.queue: deque[Request] = deque()
        self.active: list[Optional[Request]] = [None] * slots
        self.pos = np.zeros((slots,), np.int32)       # next position per slot

        # adapter identity: name -> delta; per-slot assignment; derived trees
        self.adapters: dict = {}
        self.slot_adapter: list[Optional[str]] = [None] * slots
        self._adapter_params: dict = {None: params}   # name -> full tree view
        self._mixed_fns: dict = {}       # varying-index tuple -> vmapped decode
        self._stack_sig = None           # slot_adapter snapshot the stack fits
        self._stack = None               # (vidx, [stacked leaf arrays])

        self._decode = jax.jit(self.bundle.decode_fn())
        # prefill pad widths: powers of two derived from the prompt limit
        # (replaces the old hard-coded 64-wide pad)
        self._buckets = prefill_buckets(self._prompt_limit())
        self._prefill = jax.jit(self._prefill_impl,
                                static_argnames=("plen",))
        self.stats = {"requests": 0, "prefill_tokens_submitted": 0,
                      "prefill_tokens_computed": 0, "prefix_hits": 0,
                      "prefix_tokens_reused": 0, "prefill_batches": 0,
                      "evicted_blocks": 0}

    # ------------------------------------------------------------------ #
    # Adapters
    # ------------------------------------------------------------------ #
    def register_adapter(self, name: str, delta) -> None:
        """Attach a named ``AdapterDelta`` over the frozen base.  Applying a
        delta is pure leaf replacement, so the per-adapter 'full tree' is a
        view sharing every unchanged buffer with the base — registering many
        adapters costs only their delta buffers.  Re-registering the same
        delta object is a no-op (the cache-hit path); re-registering a
        DIFFERENT delta under an existing name invalidates that name's radix
        scope (its cached prefix KV was computed under the old weights)."""
        if self.adapters.get(name) is delta:
            return
        if self.radix is not None and name in self.adapters:
            self.radix.drop_scope(name)
        self._adapter_params[name] = delta.apply(self.params)  # shape check
        self.adapters[name] = delta
        self._stack_sig = None          # stacked leaves may be stale

    def _params_for(self, adapter: Optional[str]):
        return self._adapter_params[adapter]

    # ------------------------------------------------------------------ #
    def _prefill_impl(self, params, tokens, plen):
        """Single-request prefill on a width-``plen`` padded prompt; returns
        (last_logits, per-layer kv (L,plen,KV,hd) pair, ssm/rwkv state)."""
        cfg = self.cfg
        from repro.models import attention as attn_lib, ssm as ssm_lib
        from repro.models import rwkv6 as rwkv_lib
        from repro.models import transformer
        if cfg.family == "ssm":
            logits, st = rwkv_lib.forward(cfg, params, tokens=tokens,
                                          state=rwkv_lib.init_rwkv_state(cfg, 1))
            return logits, None, st
        cache = attn_lib.init_cache(cfg, 1, plen, cfg.param_dtype)
        ssm_state = ssm_lib.init_ssm_state(cfg, 1) if cfg.family == "hybrid" else None
        r = transformer.forward(cfg, params, tokens=tokens, cache=cache,
                                cache_pos=None, ssm_state=ssm_state)
        return r.logits, r.cache, r.ssm_state

    def _prompt_limit(self) -> int:
        """Longest admissible prompt: the slot's KV capacity must hold the
        whole prefix (SWA caches are ``sliding_window`` wide; paged tables
        hold ceil(max_len/block) blocks) and one decode position must remain
        below ``max_len``."""
        limit = self.max_len - 1
        if not self.paged and self.cache is not None:
            limit = min(limit, int(self.cache["k"].shape[2]))
        return limit

    def submit(self, req: Request) -> None:
        limit = self._prompt_limit()
        if len(req.prompt_ids) > limit:
            # admitting would write a truncated prefix into the slot's KV
            # and decode against silently-corrupt context — refuse here
            raise ValueError(
                f"request {req.rid}: prompt of {len(req.prompt_ids)} tokens "
                f"exceeds this engine's limit of {limit} (max_len="
                f"{self.max_len}); raise max_len or truncate the prompt "
                "upstream")
        if req.adapter is not None and req.adapter not in self.adapters:
            raise KeyError(
                f"request {req.rid}: adapter {req.adapter!r} is not "
                f"registered (have: {sorted(self.adapters)[:8]}); call "
                "register_adapter first")
        req.times.setdefault("queued", time.perf_counter())
        self.queue.append(req)

    def _activate(self, slot: int, req: Request) -> None:
        self.active[slot] = req
        if self.slot_adapter[slot] != req.adapter:
            self.slot_adapter[slot] = req.adapter
            self._stack_sig = None
        self.pos[slot] = len(req.prompt_ids)
        req.times.setdefault("prefill", time.perf_counter())

    def _release_slot(self, slot: int) -> None:
        self.active[slot] = None
        if self.paged:
            for b in self.tables[slot]:
                self.pool.unref(b)
            self.tables[slot] = []

    def _admit(self) -> None:
        if self.paged:
            self._admit_paged()
            return
        for slot in range(self.slots):
            if self.active[slot] is not None or not self.queue:
                continue
            req = self.queue.popleft()
            npr = len(req.prompt_ids)
            if self.cfg.family in ("ssm", "hybrid"):
                # recurrent state integrates every token it sees: prefill
                # EXACT length (padding after the prompt would corrupt the
                # carried state); jit buckets by prompt length.
                plen = npr
            else:
                plen = bucket_for(npr, self._buckets)
            toks = np.zeros((1, plen), np.int32)
            toks[0, :npr] = req.prompt_ids
            logits, kv, state = self._prefill(self._params_for(req.adapter),
                                              jnp.asarray(toks), plen=plen)
            # write this request's prefix into the engine-wide slot caches
            if self.cache is not None and kv is not None:
                span = min(npr, self.cache["k"].shape[2])
                self.cache["k"] = self.cache["k"].at[:, slot, :span].set(
                    kv["k"][:, 0, :span])
                self.cache["v"] = self.cache["v"].at[:, slot, :span].set(
                    kv["v"][:, 0, :span])
                self.cache["pos"] = self.cache["pos"].at[:, slot, :span].set(
                    jnp.arange(span, dtype=jnp.int32)[None])
                self.cache["pos"] = self.cache["pos"].at[:, slot, span:].set(-1)
            if self.state is not None and state is not None:
                self.state = jax.tree_util.tree_map(
                    lambda full, one: full.at[:, slot].set(one[:, 0]),
                    self.state, state)
            # first generated token from the last prompt logit
            last = logits[0, npr - 1, :self.cfg.vocab_size]
            tok = self._sample(last, req.temperature)
            req.out_ids.append(int(tok))
            self.stats["requests"] += 1
            self.stats["prefill_tokens_submitted"] += npr
            self.stats["prefill_tokens_computed"] += npr
            self.stats["prefill_batches"] += 1
            self._activate(slot, req)

    # ------------------------------------------------------------------ #
    # Paged admission: radix match -> bucketed batched suffix prefill
    # ------------------------------------------------------------------ #
    def _alloc_blocks(self, n: int) -> list:
        if n == 0:
            return []
        if self.radix is not None and n > self.pool.n_free:
            self.stats["evicted_blocks"] += self.radix.evict(
                n - self.pool.n_free)
        return self.pool.alloc(n)

    def _gather_blocks(self, tabs: np.ndarray):
        """Assemble (L, B, nblk·block, KV, hd) K and V from per-row block
        tables ``tabs (B, nblk)`` (trash-padded).  XLA advanced-indexing
        gather by default; the pallas kernel under REPRO_BACKEND=pallas."""
        L, NT, KV, hd = self.pool.k.shape
        B, nblk = tabs.shape
        flat = jnp.asarray(tabs.reshape(-1), jnp.int32)
        xk = self.pool.k.reshape(L, NT, KV * hd)
        xv = self.pool.v.reshape(L, NT, KV * hd)
        if self._gather_pallas:
            from repro.kernels import interpret_default, paged_gather
            interpret = interpret_default()
            gk = paged_gather(xk, flat, self.block, interpret=interpret)
            gv = paged_gather(xv, flat, self.block, interpret=interpret)
        else:
            from repro.kernels.paged import paged_gather_ref
            gk = paged_gather_ref(xk, flat, self.block)
            gv = paged_gather_ref(xv, flat, self.block)
        shape = (L, B, nblk * self.block, KV, hd)
        return gk.reshape(shape), gv.reshape(shape)

    def _admit_paged(self) -> None:
        free = [s for s in range(self.slots) if self.active[s] is None]
        pending = []
        while free and self.queue:
            pending.append((free.pop(0), self.queue.popleft()))
        if not pending:
            return
        blk = self.block
        plans = []
        for slot, req in pending:
            if self.radix is not None:
                cached, nc = self.radix.match(req.adapter, req.prompt_ids)
            else:
                cached, nc = [], 0
            npr = len(req.prompt_ids)
            new_blocks = self._alloc_blocks(-(-npr // blk) - nc // blk)
            for b in cached:
                self.pool.ref(b)          # slot's own pin on shared prefix
            st = self.stats
            st["requests"] += 1
            st["prefill_tokens_submitted"] += npr
            st["prefill_tokens_computed"] += npr - nc
            if nc:
                st["prefix_hits"] += 1
                st["prefix_tokens_reused"] += nc
            plans.append((slot, req, nc, cached, new_blocks))
        groups: dict = {}
        for plan in plans:
            _, req, nc, _, _ = plan
            pcap = blk * pow2ceil(nc // blk) if nc else 0
            scap = bucket_for(len(req.prompt_ids) - nc, self._buckets)
            groups.setdefault((req.adapter, pcap, scap), []).append(plan)
        for (adapter, pcap, scap), grp in groups.items():
            self._prefill_group(adapter, pcap, scap, grp)

    def _prefill_group(self, adapter, pcap: int, scap: int, grp: list) -> None:
        """One jitted chunk-prefill for every queued request sharing
        (adapter, prefix-pad, suffix-bucket): gather cached prefix KV from
        the pool, run the batched suffix forward, scatter the new suffix KV
        into each request's fresh blocks, thread the full chunks into the
        radix cache, and activate the slots."""
        cfg = self.cfg
        blk = self.block
        L, _, KV, hd = self.pool.k.shape
        B = len(grp)
        dtype = cfg.param_dtype
        toks = np.zeros((B, scap), np.int32)
        plens = np.zeros((B,), np.int32)
        for i, (_, req, nc, _, _) in enumerate(grp):
            suf = req.prompt_ids[nc:]
            toks[i, :len(suf)] = suf
            plens[i] = nc
        if pcap:
            tabs = np.zeros((B, pcap // blk), np.int32)
            ppos = np.full((B, pcap), -1, np.int32)
            for i, (_, _, nc, cached, _) in enumerate(grp):
                tabs[i, :len(cached)] = cached
                ppos[i, :nc] = np.arange(nc, dtype=np.int32)
            pk, pv = self._gather_blocks(tabs)
            ppos_j = jnp.asarray(ppos)
        else:
            pk = jnp.zeros((L, B, 0, KV, hd), dtype)
            pv = jnp.zeros((L, B, 0, KV, hd), dtype)
            ppos_j = jnp.zeros((B, 0), jnp.int32)
        ck = jnp.concatenate([pk, jnp.zeros((L, B, scap, KV, hd), dtype)],
                             axis=2)
        cv = jnp.concatenate([pv, jnp.zeros((L, B, scap, KV, hd), dtype)],
                             axis=2)
        cpos = jnp.concatenate(
            [jnp.broadcast_to(ppos_j[None], (L, B, pcap)),
             jnp.full((L, B, scap), -1, jnp.int32)], axis=2)
        logits, cache = _chunk_prefill(cfg, self._params_for(adapter),
                                       jnp.asarray(toks), ck, cv, cpos,
                                       jnp.asarray(plens))
        self.stats["prefill_batches"] += 1
        # last real prompt logit per request, one bucketed gather + transfer
        s_last = np.array([len(req.prompt_ids) - nc - 1
                           for _, req, nc, _, _ in grp], np.int32)
        last = np.asarray(jnp.take_along_axis(
            logits, jnp.asarray(s_last)[:, None, None], axis=1
        )[:, 0, :cfg.vocab_size])
        # suffix KV landed at cache rows [plen, plen+scap) — row index IS the
        # absolute position.  Extract the whole bucketed window per request
        # (one gather, shape keyed on (pcap, scap) only) and scatter real
        # rows into each request's fresh blocks; pad rows go to the trash.
        sidx = (jnp.asarray(plens)[:, None]
                + jnp.arange(scap, dtype=jnp.int32)[None])
        sel = sidx[None, :, :, None, None]
        ksuf = jnp.take_along_axis(cache["k"], sel, axis=2)
        vsuf = jnp.take_along_axis(cache["v"], sel, axis=2)
        L_, B_ = ksuf.shape[:2]
        rows = np.zeros((B_ * scap,), np.int32)         # default: trash row 0
        for i, (slot, req, nc, cached, new_blocks) in enumerate(grp):
            npr = len(req.prompt_ids)
            req.out_ids.append(int(self._sample(jnp.asarray(last[i]),
                                                req.temperature)))
            for j in range(npr - nc):
                p = nc + j
                rows[i * scap + j] = (new_blocks[(p - nc) // blk] * blk
                                      + p % blk)
            self.tables[slot] = list(cached) + list(new_blocks)
            if self.radix is not None:
                chunk_blocks = (list(cached)
                                + list(new_blocks[:npr // blk - nc // blk]))
                if chunk_blocks:
                    self.radix.insert(req.adapter, req.prompt_ids,
                                      chunk_blocks)
            self._activate(slot, req)
        self.pool.write(rows,
                        ksuf.reshape(L_, B_ * scap, *ksuf.shape[3:]),
                        vsuf.reshape(L_, B_ * scap, *vsuf.shape[3:]))

    # ------------------------------------------------------------------ #
    # Paged decode: block-table gather -> registry decode -> row writeback
    # ------------------------------------------------------------------ #
    def _ensure_decode_blocks(self, live: list) -> None:
        blk = self.block
        for s in live:
            bi = int(self.pos[s]) // blk
            while len(self.tables[s]) <= bi:
                self.tables[s].extend(self._alloc_blocks(1))

    def _assemble_decode_cache(self) -> dict:
        """Dense (L, slots, T, KV, hd) view of every slot's block table,
        T = ceil(max_len/block)·block — STATIC, so the decode executable
        compiles once.  Inactive slots gather the trash block with pos=-1
        everywhere; their masked junk writes are never copied back."""
        blk = self.block
        T = self._nblk_slot * blk
        tabs = np.zeros((self.slots, self._nblk_slot), np.int32)
        valid = np.zeros((self.slots, 1), np.int32)
        for s in range(self.slots):
            tabs[s, :len(self.tables[s])] = self.tables[s]
            if self.active[s] is not None:
                valid[s, 0] = int(self.pos[s])
        gk, gv = self._gather_blocks(tabs)
        ar = np.arange(T, dtype=np.int32)[None]
        pos_rows = np.where(ar < valid, ar, -1)
        L = self.pool.k.shape[0]
        cpos = jnp.broadcast_to(jnp.asarray(pos_rows)[None],
                                (L, self.slots, T))
        return {"k": gk, "v": gv, "pos": cpos}

    def _writeback_decode(self, live: list) -> None:
        """Scatter each live slot's freshly written decode row (cache row
        pos[s] — absolute position) back into its tail pool block."""
        blk = self.block
        idx = jnp.asarray(live)
        pj = jnp.asarray(self.pos[np.asarray(live)])
        krow = self.cache["k"][:, idx, pj]             # (L, n, KV, hd)
        vrow = self.cache["v"][:, idx, pj]
        rows = np.array(
            [self.tables[s][int(self.pos[s]) // blk] * blk
             + int(self.pos[s]) % blk for s in live], np.int32)
        self.pool.write(rows, krow, vrow)
        self.cache = None

    def _sample(self, logits: jnp.ndarray, temperature: float):
        if temperature <= 0:
            return jnp.argmax(logits)
        self.key, sub = jax.random.split(self.key)
        return jax.random.categorical(sub, logits / temperature)

    # ------------------------------------------------------------------ #
    # Mixed-adapter decode
    # ------------------------------------------------------------------ #
    def _mixed_decode_fn(self, vidx: tuple):
        """One jitted vmap-over-slots decode for a given set of varying leaf
        indices.  Base leaves are closure constants (broadcast, in_axes=None
        in effect); only the ``vidx`` leaves arrive stacked with a leading
        slot axis.  Inside, each slot re-adds its size-1 batch axis so the
        registry decode runs its per-slot (continuous-batching) path."""
        if vidx in self._mixed_fns:
            return self._mixed_fns[vidx]
        decode = self.bundle.decode_fn()
        base_leaves, treedef = jax.tree_util.tree_flatten(self.params)

        def one(varying, token, cpos, cache):
            leaves = list(base_leaves)
            for i, v in zip(vidx, varying):
                leaves[i] = v
            params = jax.tree_util.tree_unflatten(treedef, leaves)
            batch = {"token": token[None],                       # (1, 1)
                     "cache_pos": cpos[None],                    # (1,)
                     "cache": jax.tree_util.tree_map(
                         lambda a: a[:, None], cache)}           # (L,1,...)
            logits, cache_out = decode(params, batch)
            return logits[0], jax.tree_util.tree_map(
                lambda a: a[:, 0], cache_out)

        fn = jax.jit(jax.vmap(one, in_axes=(0, 0, 0, 1), out_axes=(0, 1)))
        self._mixed_fns[vidx] = fn
        return fn

    def _stacked_leaves(self):
        """(vidx, stacked) for the current slot→adapter assignment: the union
        of the live adapters' changed-leaf indices, each stacked (slot axis 0)
        from the per-adapter value or the base leaf.  Rebuilt only when the
        assignment changes (``_stack_sig``)."""
        sig = tuple(self.slot_adapter)
        if self._stack_sig == sig:
            return self._stack
        base_leaves, _ = jax.tree_util.tree_flatten(self.params)
        names = {a for a in sig if a is not None}
        vidx = tuple(sorted({i for n in names
                             for i in self.adapters[n].indices}))
        by_name = {n: dict(zip(self.adapters[n].indices,
                               self.adapters[n].values)) for n in names}
        stacked = [jnp.stack([by_name.get(a, {}).get(i, base_leaves[i])
                              for a in sig], axis=0) for i in vidx]
        self._stack_sig, self._stack = sig, (vidx, stacked)
        return self._stack

    def _grouped_decode(self, toks, live):
        """Fallback: one decode per distinct live adapter.  Every group call
        decodes the full slot batch against the PRE-step cache/state, then
        only that group's slot rows (cache/state axis 1, logits axis 0) are
        merged into the step result — other slots' rows stay untouched."""
        groups: dict = {}
        for s in live:
            groups.setdefault(self.slot_adapter[s], []).append(s)
        pre_cache, pre_state = self.cache, self.state
        new_cache, new_state = pre_cache, pre_state
        logits_all = None
        for name, slots_g in groups.items():
            batch = {"token": jnp.asarray(toks),
                     "cache_pos": jnp.asarray(self.pos, jnp.int32)}
            params = self._params_for(name)
            if self.cfg.family == "ssm":
                batch["state"] = pre_state
                logits, state_g = self._decode(params, batch)
                cache_g = None
            elif self.cfg.family == "hybrid":
                batch["cache"], batch["state"] = pre_cache, pre_state
                logits, (cache_g, state_g) = self._decode(params, batch)
            else:
                batch["cache"] = pre_cache
                logits, cache_g = self._decode(params, batch)
                state_g = None
            idx = jnp.asarray(slots_g)
            if logits_all is None:
                logits_all = logits
            else:
                logits_all = logits_all.at[idx].set(logits[idx])
            if cache_g is not None:
                new_cache = jax.tree_util.tree_map(
                    lambda acc, out: acc.at[:, idx].set(out[:, idx]),
                    new_cache, cache_g)
            if state_g is not None:
                new_state = jax.tree_util.tree_map(
                    lambda acc, out: acc.at[:, idx].set(out[:, idx]),
                    new_state, state_g)
        self.cache, self.state = new_cache, new_state
        return logits_all

    # ------------------------------------------------------------------ #
    def step(self) -> int:
        """One lockstep decode over all live slots; returns #live slots."""
        self._admit()
        live = [s for s, r in enumerate(self.active) if r is not None]
        if not live:
            return 0
        if self.paged:
            self._ensure_decode_blocks(live)
            self.cache = self._assemble_decode_cache()
        toks = np.zeros((self.slots, 1), np.int32)
        for s in live:
            toks[s, 0] = self.active[s].out_ids[-1]
        names = {self.slot_adapter[s] for s in live}
        if names == {None}:
            # per-slot positions: every row decodes at its own absolute
            # position (continuous batching); inactive rows write masked junk
            # that the next admission overwrites.
            batch = {"token": jnp.asarray(toks),
                     "cache_pos": jnp.asarray(self.pos, jnp.int32)}
            if self.cfg.family == "ssm":
                batch["state"] = self.state
                logits, self.state = self._decode(self.params, batch)
            elif self.cfg.family == "hybrid":
                batch["cache"], batch["state"] = self.cache, self.state
                logits, (self.cache, self.state) = self._decode(self.params,
                                                                batch)
            else:
                batch["cache"] = self.cache
                logits, self.cache = self._decode(self.params, batch)
        elif self.cfg.family in ("dense", "moe") and not any(
                self.adapters[n].full_tree for n in names if n is not None):
            vidx, stacked = self._stacked_leaves()
            fn = self._mixed_decode_fn(vidx)
            logits, self.cache = fn(stacked, jnp.asarray(toks),
                                    jnp.asarray(self.pos, jnp.int32),
                                    self.cache)
        else:
            logits = self._grouped_decode(toks, live)
        if self.paged:
            self._writeback_decode(live)
        now = time.perf_counter()
        for s in live:
            req = self.active[s]
            tok = int(self._sample(logits[s, 0, :self.cfg.vocab_size],
                                   req.temperature))
            req.out_ids.append(tok)
            req.times.setdefault("decode", now)
            self.pos[s] += 1
            if ((self.eos_id is not None and tok == self.eos_id)
                    or len(req.out_ids) >= req.max_new_tokens
                    or self.pos[s] >= self.max_len - 1):
                req.done = True
                req.times["done"] = time.perf_counter()
                self._release_slot(s)
        return len(live)

    def prefix_stats(self) -> dict:
        """Prefill-economy counters: tokens submitted vs actually computed,
        request-level prefix hits, blocks evicted.  ``token_reuse_rate`` is
        the fraction of submitted prompt tokens served from the radix cache."""
        st = dict(self.stats)
        st["prefix_hit_rate"] = (st["prefix_hits"] / st["requests"]
                                 if st["requests"] else 0.0)
        st["token_reuse_rate"] = (
            st["prefix_tokens_reused"] / st["prefill_tokens_submitted"]
            if st["prefill_tokens_submitted"] else 0.0)
        if self.pool is not None:
            st["pool_blocks"] = self.pool.n_blocks
            st["pool_free_blocks"] = self.pool.n_free
            st["radix_nodes"] = self.radix.n_nodes if self.radix else 0
        return st

    def run(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if not self.queue and all(r is None for r in self.active):
                break
            self.step()
