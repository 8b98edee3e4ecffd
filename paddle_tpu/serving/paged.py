"""Paged KV-cache serving: block-pool decode, chunked prefill, prefix reuse.

Reference parity: the reference's decode caches (DecoderCache and the
beam-search state reuse in the fused decoding ops) give every live
sequence a dense ``max_len`` K/V slab — HBM is priced at the worst case
whether a sequence holds 3 tokens or 3000, which is exactly why PR 8's
``ContinuousBatcher`` caps out at ``num_slots × max_len`` rows of resident
state (ROADMAP item 1).  TPU-native design: K/V lives in a **pool of
fixed-size blocks** (``block_size`` tokens each) and a sequence's cache is
a *block table* — the ordered list of physical block ids holding its
tokens.  HBM now follows LIVE tokens, the device arrays never change
shape (steady state stays at zero retraces), and the host allocator
runs between decode iterations where it costs nothing.

Three things fall out of the indirection:

* **Block-pool decode** — ``PagedDecoder`` keeps the ContinuousBatcher
  surface (``try_join/join/evict/step/decode/run_until_idle``) but decode
  attention runs through ``ops/pallas/paged_attention``: the table rides
  the kernel's scalar-prefetch operand and block gathers happen at the
  grid level.  Freeing a sequence is a host-side refcount decrement — no
  device clear pass (the old path's ``_clear_fn``), because masked
  lengths make stale block contents unreachable.
* **Chunked prefill** — long prompts are written in fixed-size chunks of
  ``prefill_chunk`` tokens, one chunk per ``step()``, round-robin across
  prefilling sequences and interleaved with the decode batch.  A chunk is
  C pseudo-sequences sharing the table with per-row lengths
  ``start+1 … start+C`` (write K/V first, then attend) — causal semantics
  with the SAME kernel and only two compiled step shapes total, so a
  3000-token prompt arrival cannot stall short-request TTFT behind a
  monolithic prefill.
* **Cross-tenant prefix caching** — every FULL prompt block gets a chain
  content hash (model fingerprint ⊕ previous-block hash ⊕ block tokens);
  a joining prompt whose leading blocks hash-hit resolves them to the
  SAME physical blocks with a refcount bump and skips their prefill
  entirely.  K/V depends only on (token, position), and the chain hash
  pins both, so shared blocks are bitwise the blocks the sequence would
  have written.  Shared blocks are always full and never written again
  (writes only land past the shared prefix), so no copy-on-write is
  needed.  Decoders sharing one ``PagedKVCache`` share the pool across
  tenants; the model fingerprint namespaces the hashes.

int8 KV blocks: ``kv_dtype="int8"`` stores blocks quantized with
per-block fp32 (k, v) scales — PR 13's PTQ story at block granularity.
The toy model's scales are calibrated exactly (amax over the full
vocab × position grid), dequant runs next to the dot in the kernel, and
``serve.kv_cache_bytes`` reports the compressed footprint.

Pool pressure: ``join`` takes the prompt's blocks up front and decode
allocates on demand at block boundaries.  Exhaustion first reclaims LRU
prefix-cache entries; if the pool is still dry, a joiner is refused
(``serve.load_shed{reason="kv_blocks"}``) and a decoding sequence is
evicted mid-stream with its tokens intact (the ContinuousBatcher evict
contract).

``dense_reference_decode`` is the parity oracle: a straight-line dense
decode of one sequence.  tests/test_paged.py pins paged tokens per
sequence token-bitwise against it, prefix-hit bitwise identity, and the
alloc/free refcount physics under join/evict churn.
"""
from __future__ import annotations

import hashlib
import math
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import flags as _flags
from ..ops.pallas import paged_attention as _pa
from ..utils import monitor as _monitor
from .continuous import DecodeHandle
from .slo import AdmissionError, LOAD_SHED, REQUEST_MS, TTFT_MS

__all__ = [
    "BlockPool", "PrefixCache", "PagedKVCache", "PagedDecoder",
    "make_paged_toy_lm", "dense_reference_decode", "kv_pool_bytes",
]

KV_BLOCKS_FREE = _monitor.gauge(
    "serve.kv_blocks_free", "Free blocks in the paged KV pool (null block "
    "and refcounted live/cached blocks excluded).")
KV_CACHE_BYTES = _monitor.gauge(
    "serve.kv_cache_bytes", "Device bytes held by the paged KV cache "
    "(K + V blocks at their storage dtype + per-block scales — the "
    "compressed footprint under int8 blocks).")
KV_PREFIX_HITS = _monitor.counter(
    "serve.kv_prefix_hits", "Prompt blocks resolved from the cross-tenant "
    "prefix cache instead of prefilled (one count per reused block).")
KV_PREFILL_CHUNKS = _monitor.counter(
    "serve.kv_prefill_chunks", "Chunked-prefill steps executed (one count "
    "per prompt chunk written into the block pool).")

_FREE, _PREFILL, _DECODE = 0, 1, 2

# Physical block 0 is the *null block*: never allocated, the padding
# target for inactive table entries and masked scatter rows, so every
# table entry the kernel DMAs is a valid block id.
_NULL_BLOCK = 0


def kv_pool_bytes(num_blocks: int, block_size: int, hidden: int,
                  kv_dtype: str = "float32") -> int:
    """Device bytes for a pool config (K + V + scales, null block
    included) — the same number ``PagedKVCache`` allocates and memcheck's
    MC008 prices, exported so both agree by construction."""
    itemsize = jnp.dtype(kv_dtype).itemsize
    total = num_blocks + 1
    return 2 * total * block_size * hidden * itemsize + total * 2 * 4


class BlockPool:
    """Host-side refcounted allocator over physical block ids.

    ``alloc`` hands out an id at refcount 1; ``share`` bumps it (a prefix
    hit or a cache insert); ``free`` drops it and returns the block to the
    freelist at zero.  Over-free raises — the double-free physics the
    churn test pins."""

    def __init__(self, num_blocks: int):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        self.num_blocks = int(num_blocks)           # allocatable blocks
        total = self.num_blocks + 1                 # + null block
        self._rc = [0] * total
        self._rc[_NULL_BLOCK] = 1                   # pinned forever
        self._free = list(range(total - 1, _NULL_BLOCK, -1))  # pop() -> 1,2,…

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def live_count(self) -> int:
        return self.num_blocks - len(self._free)

    def alloc(self) -> Optional[int]:
        if not self._free:
            return None
        bid = self._free.pop()
        assert self._rc[bid] == 0
        self._rc[bid] = 1
        return bid

    def share(self, bid: int) -> int:
        if self._rc[bid] <= 0:
            raise RuntimeError(f"share of unallocated block {bid}")
        self._rc[bid] += 1
        return bid

    def free(self, bid: int) -> None:
        if bid == _NULL_BLOCK:
            raise RuntimeError("free of the null block")
        if self._rc[bid] <= 0:
            raise RuntimeError(f"double free of block {bid}")
        self._rc[bid] -= 1
        if self._rc[bid] == 0:
            self._free.append(bid)

    def refcount(self, bid: int) -> int:
        return self._rc[bid]


class PrefixCache:
    """LRU map of chain content hash -> physical block id.  The cache owns
    one reference per entry, so cached blocks survive their writer; a hit
    is a ``share`` (the joiner gets its own reference).  ``reclaim`` drops
    LRU entries under pool pressure — an entry whose block is still
    referenced by live sequences frees nothing yet but will when they
    retire."""

    def __init__(self, pool: BlockPool):
        self._pool = pool
        self._map: "OrderedDict[str, int]" = OrderedDict()

    def __len__(self) -> int:
        return len(self._map)

    def get(self, h: str) -> Optional[int]:
        bid = self._map.get(h)
        if bid is None:
            return None
        self._map.move_to_end(h)
        KV_PREFIX_HITS.inc()
        return self._pool.share(bid)

    def put(self, h: str, bid: int) -> None:
        if h in self._map:
            return
        self._pool.share(bid)                       # the cache's reference
        self._map[h] = bid

    def reclaim(self, need: int) -> int:
        """Drop LRU entries until ``need`` blocks actually returned to the
        freelist (or the cache is empty); returns how many were freed."""
        freed = 0
        while self._map and freed < need:
            _, bid = self._map.popitem(last=False)
            was_free = self._pool.free_count
            self._pool.free(bid)
            freed += self._pool.free_count - was_free
        return freed


class PagedToyLM:
    """Deterministic single-attention-layer greedy LM for the paged path.

    K/V for a token depend ONLY on (token, absolute position) — the
    property that makes chunk K/V writes order-free and prefix blocks
    position-exact reusable.  ``fingerprint`` namespaces prefix hashes so
    cross-tenant sharing only pairs identical models."""

    def __init__(self, vocab: int, hidden: int, max_positions: int,
                 seed: int):
        self.vocab, self.hidden = int(vocab), int(hidden)
        self.max_positions = int(max_positions)
        key = jax.random.PRNGKey(seed)
        k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
        s = 0.1
        self.emb = jax.random.normal(k1, (vocab, hidden), jnp.float32) * s
        self.pe = jax.random.normal(k2, (max_positions, hidden),
                                    jnp.float32) * s
        self.wq = jax.random.normal(k3, (hidden, hidden), jnp.float32) * s
        self.wk = jax.random.normal(k4, (hidden, hidden), jnp.float32) * s
        self.wv = jax.random.normal(k5, (hidden, hidden), jnp.float32) * s
        self.wo = jax.random.normal(k6, (hidden, vocab), jnp.float32) * s
        self.fingerprint = hashlib.sha256(
            f"paged_toy_lm:v1:{vocab}:{hidden}:{max_positions}:{seed}"
            .encode()).hexdigest()[:16]

    def qkv(self, tokens, positions):
        """(q, k, v) fp32 rows for int32 tokens at absolute positions."""
        x = self.emb[tokens] + self.pe[positions]
        return x @ self.wq, x @ self.wk, x @ self.wv

    def calibrate_kv_scales(self) -> Tuple[float, float]:
        """Exact PTQ calibration: amax of K and V over the full
        vocab × position grid (the toy model's entire activation space),
        symmetric int8."""
        toks = jnp.arange(self.vocab, dtype=jnp.int32)
        pos = jnp.arange(self.max_positions, dtype=jnp.int32)
        x = (self.emb[toks][:, None, :] + self.pe[pos][None, :, :])
        amax_k = float(jnp.max(jnp.abs(x @ self.wk)))
        amax_v = float(jnp.max(jnp.abs(x @ self.wv)))
        return max(amax_k, 1e-8) / 127.0, max(amax_v, 1e-8) / 127.0


def make_paged_toy_lm(vocab: int = 64, hidden: int = 32,
                      max_positions: int = 512, seed: int = 0) -> PagedToyLM:
    return PagedToyLM(vocab, hidden, max_positions, seed)


class PagedKVCache:
    """The shared device-side store: K/V block arrays, per-block scales,
    the host allocator, and the prefix cache.  Multiple ``PagedDecoder``
    instances (tenants serving the same model) attach to ONE cache — that
    sharing is what makes the prefix cache cross-tenant."""

    def __init__(self, model: PagedToyLM, num_blocks: int, block_size: int,
                 kv_dtype: str = "float32"):
        if kv_dtype not in ("float32", "int8"):
            raise ValueError(f"kv_dtype must be float32|int8, got {kv_dtype}")
        self.model = model
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.kv_dtype = kv_dtype
        total = self.num_blocks + 1
        dt = jnp.dtype(kv_dtype)
        self.k = jnp.zeros((total, self.block_size, model.hidden), dt)
        self.v = jnp.zeros((total, self.block_size, model.hidden), dt)
        if kv_dtype == "int8":
            ks, vs = model.calibrate_kv_scales()
            self.k_scale, self.v_scale = ks, vs
            self.scales = jnp.tile(
                jnp.asarray([[ks, vs]], jnp.float32), (total, 1))
        else:
            self.k_scale = self.v_scale = 1.0
            self.scales = jnp.ones((total, 2), jnp.float32)
        self.pool = BlockPool(self.num_blocks)
        self.prefix = PrefixCache(self.pool)
        self.bytes = kv_pool_bytes(self.num_blocks, self.block_size,
                                   model.hidden, kv_dtype)
        KV_CACHE_BYTES.set(float(self.bytes))
        self.sync_metrics()

    def sync_metrics(self) -> None:
        KV_BLOCKS_FREE.set(float(self.pool.free_count))

    def block_hashes(self, tokens: Sequence[int]) -> List[str]:
        """Chain hashes for every FULL block of ``tokens``: block i's hash
        commits to the model, the storage dtype, every earlier block, and
        its own tokens — equal hash ⟺ bitwise-equal block contents."""
        out, prev = [], f"{self.model.fingerprint}:{self.kv_dtype}"
        bs = self.block_size
        for i in range(len(tokens) // bs):
            blk = ",".join(str(int(t)) for t in tokens[i * bs:(i + 1) * bs])
            prev = hashlib.sha256(f"{prev}|{blk}".encode()).hexdigest()
            out.append(prev)
        return out


class _Seq:
    __slots__ = ("handle", "block_ids", "context_len", "hashes",
                 "shared_blocks", "cached_upto")

    def __init__(self, handle: DecodeHandle, block_ids: List[int],
                 context_len: int, hashes: List[str], shared_blocks: int):
        self.handle = handle
        self.block_ids = block_ids       # owned references, table order
        self.context_len = context_len   # K/V tokens stored so far
        self.hashes = hashes             # full-prompt-block chain hashes
        self.shared_blocks = shared_blocks
        self.cached_upto = shared_blocks  # blocks already in PrefixCache


class PagedDecoder:
    """Iteration-level decoder over a paged KV pool — the
    ``ContinuousBatcher`` surface (join/evict/step/decode/run_until_idle/
    active_count) re-backed by block tables.

    ``max_seqs`` bounds the decode batch width (the compiled step shape);
    ``max_blocks_per_seq`` bounds one sequence's table.  Two jitted
    functions exist: the decode step ``[max_seqs]`` and the prefill chunk
    ``[prefill_chunk]`` — both shapes are fixed at construction, so steady
    state never retraces regardless of joins, evictions, prompt lengths,
    or pool churn (pinned by ``executor.traces`` in tests)."""

    def __init__(self, model: PagedToyLM, cache: PagedKVCache,
                 max_seqs: int, max_blocks_per_seq: int,
                 prefill_chunk: int = 8, donate: Optional[bool] = None,
                 tenant: str = "default"):
        from ..static import executor as _ex

        if model is not cache.model:
            raise ValueError("decoder model must be the cache's model")
        if max_seqs < 1:
            raise ValueError(f"max_seqs must be >= 1, got {max_seqs}")
        self.model = model
        self.cache = cache
        self.max_seqs = int(max_seqs)
        self.max_blocks_per_seq = int(max_blocks_per_seq)
        self.prefill_chunk = int(prefill_chunk)
        self.max_len = self.max_blocks_per_seq * cache.block_size
        self.tenant = str(tenant)
        if donate is None:
            donate = (bool(_flags.get_flag("donate_state"))
                      and _ex._donation_async_safe())

        bs = cache.block_size
        quantized = cache.kv_dtype == "int8"
        k_scale, v_scale = cache.k_scale, cache.v_scale
        scales = cache.scales

        def _store(vals, scale):
            if not quantized:
                return vals
            q = jnp.round(vals / scale)
            return jnp.clip(q, -127, 127).astype(jnp.int8)

        def _write(kc, vc, bids, offs, k_new, v_new, active):
            # Masked scatter: inactive rows target the null block and
            # rewrite its existing value, so duplicates are benign and the
            # executable never depends on how many rows are live.
            k_cur = kc[bids, offs]
            v_cur = vc[bids, offs]
            am = active[:, None]
            kc = kc.at[bids, offs].set(
                jnp.where(am, _store(k_new, k_scale), k_cur))
            vc = vc.at[bids, offs].set(
                jnp.where(am, _store(v_new, v_scale), v_cur))
            return kc, vc

        # ``meta`` packs the five per-row scalars (tokens, positions, lens,
        # bids, offs) into ONE (5, rows) int32 host->device transfer per
        # step — at serving step rates the per-array dispatch overhead of
        # five separate feeds is the dominant host cost.  ``lens > 0``
        # encodes activity (a live row always sees >= 1 token).
        def _decode_step(kc, vc, tables, meta):
            _ex._m_traces.inc()   # host side effect: fires at trace time
            tokens, positions, lens, bids, offs = (meta[i] for i in range(5))
            active = lens > 0
            q, k_new, v_new = model.qkv(tokens, positions)
            kc, vc = _write(kc, vc, bids, offs, k_new, v_new, active)
            attn = _pa.paged_attention(q, kc, vc, tables, lens,
                                       kv_scales=scales)
            nxt = jnp.argmax(attn @ model.wo, axis=-1).astype(jnp.int32)
            return kc, vc, jnp.where(active, nxt, 0)

        def _prefill_step(kc, vc, table, meta):
            _ex._m_traces.inc()
            tokens, positions, lens, bids, offs = (meta[i] for i in range(5))
            active = lens > 0
            q, k_new, v_new = model.qkv(tokens, positions)
            kc, vc = _write(kc, vc, bids, offs, k_new, v_new, active)
            # C pseudo-sequences share the table; per-row length
            # position+1 gives exact causal attention inside the chunk
            # because the chunk's K/V was written first.
            tables = jnp.broadcast_to(table, (tokens.shape[0],
                                              table.shape[0]))
            attn = _pa.paged_attention(q, kc, vc, tables, lens,
                                       kv_scales=scales)
            nxt = jnp.argmax(attn @ model.wo, axis=-1).astype(jnp.int32)
            return kc, vc, nxt

        dn = (0, 1) if donate else ()
        self._decode_fn = jax.jit(_decode_step, donate_argnums=dn)
        self._prefill_fn = jax.jit(_prefill_step, donate_argnums=dn)
        # persistent host mirrors, updated incrementally (join/grow/retire)
        # instead of rebuilt per step
        self._tables_np = np.full((self.max_seqs, self.max_blocks_per_seq),
                                  _NULL_BLOCK, np.int32)
        self._meta_np = np.zeros((5, self.max_seqs), np.int32)
        self._pf_meta_np = np.zeros((5, self.prefill_chunk), np.int32)
        self._slots: List[Optional[_Seq]] = [None] * self.max_seqs
        self._state = [_FREE] * self.max_seqs
        self._prefill_rr: List[int] = []   # round-robin queue of slot ids
        self._shed_reason = "slots"        # why the last try_join refused

    # -- admission -----------------------------------------------------------
    @property
    def active_count(self) -> int:
        return sum(1 for s in self._state if s != _FREE)

    def _release(self, bids: List[int]) -> None:
        for bid in bids:
            self.cache.pool.free(bid)

    def try_join(self, prompt: Sequence[int],
                 max_new_tokens: int) -> Optional[DecodeHandle]:
        """Claim a slot and the prompt's blocks; None when slots or blocks
        are unavailable (callers distinguish via ``join``)."""
        h = DecodeHandle(prompt, max_new_tokens)
        if not h.prompt:
            raise ValueError("empty prompt")
        if len(h.prompt) + h.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({len(h.prompt)}) + max_new_tokens "
                f"({h.max_new_tokens}) exceeds max_blocks_per_seq * "
                f"block_size ({self.max_len})")
        if (len(h.prompt) + h.max_new_tokens
                > self.model.max_positions):
            raise ValueError("request exceeds the model's max_positions")
        slot = next((i for i in range(self.max_seqs)
                     if self._state[i] == _FREE), None)
        if slot is None:
            self._shed_reason = "slots"
            return None

        cache, bs = self.cache, self.cache.block_size
        plen = len(h.prompt)
        hashes = cache.block_hashes(h.prompt)
        # Shareable prefix: full blocks strictly before the last prompt
        # token — at least one token always prefills, producing the
        # first-generated-token logits.
        limit = min(len(hashes), (plen - 1) // bs)
        block_ids: List[int] = []
        for i in range(limit):
            bid = cache.prefix.get(hashes[i])
            if bid is None:
                break
            block_ids.append(bid)
        shared = len(block_ids)
        need = _ceil_div(plen, bs) - shared
        for _ in range(need):
            bid = cache.pool.alloc()
            if bid is None and cache.prefix.reclaim(1):
                bid = cache.pool.alloc()
            if bid is None:
                self._release(block_ids)
                self._shed_reason = "kv_blocks"
                cache.sync_metrics()
                return None
            block_ids.append(bid)

        self._state[slot] = _PREFILL
        self._slots[slot] = _Seq(h, block_ids, shared * bs, hashes, shared)
        self._prefill_rr.append(slot)
        h.slot = slot
        row = self._tables_np[slot]
        row[:] = _NULL_BLOCK
        row[:len(block_ids)] = block_ids
        cache.sync_metrics()
        return h

    def join(self, prompt: Sequence[int],
             max_new_tokens: int) -> DecodeHandle:
        self._shed_reason = "slots"
        h = self.try_join(prompt, max_new_tokens)
        if h is None:
            reason = self._shed_reason
            LOAD_SHED.inc(reason=reason)
            raise AdmissionError(
                f"paged decode pool full ({reason}): "
                f"{self.max_seqs} seqs, "
                f"{self.cache.pool.free_count} free blocks")
        return h

    def evict(self, handle: DecodeHandle) -> None:
        """Retire a sequence mid-decode; generated tokens stay on the
        handle, its block references are dropped (physical blocks outlive
        it only while the prefix cache or another sequence holds them)."""
        if handle.done or handle.slot is None:
            return
        slot = handle.slot
        seq = self._slots[slot]
        if seq is None or seq.handle is not handle:
            return
        handle.evicted = True
        self._retire(slot)

    def _retire(self, slot: int) -> None:
        seq = self._slots[slot]
        self._state[slot] = _FREE
        self._slots[slot] = None
        if slot in self._prefill_rr:
            self._prefill_rr.remove(slot)
        self._tables_np[slot, :] = _NULL_BLOCK
        if seq is not None:
            self._release(seq.block_ids)
            h = seq.handle
            h.done = True
            h.slot = None
            REQUEST_MS.observe((time.perf_counter() - h._t_submit) * 1e3,
                               tenant=self.tenant, bucket="decode")
        self.cache.sync_metrics()

    # -- block bookkeeping ---------------------------------------------------
    def _grow(self, seq: _Seq) -> bool:
        """Ensure a block exists for position ``seq.context_len``; False
        when the pool (and the reclaimable prefix cache) is dry."""
        idx = seq.context_len // self.cache.block_size
        if idx < len(seq.block_ids):
            return True
        bid = self.cache.pool.alloc()
        if bid is None and self.cache.prefix.reclaim(1):
            bid = self.cache.pool.alloc()
        if bid is None:
            return False
        seq.block_ids.append(bid)
        self._tables_np[seq.handle.slot, idx] = bid
        self.cache.sync_metrics()
        return True

    def _publish_full_blocks(self, seq: _Seq) -> None:
        """Insert freshly-completed FULL prompt blocks into the prefix
        cache (shared ones are already there, by definition of the hit)."""
        plen = len(seq.handle.prompt)
        full = min(seq.context_len, plen) // self.cache.block_size
        while seq.cached_upto < min(full, len(seq.hashes)):
            i = seq.cached_upto
            self.cache.prefix.put(seq.hashes[i], seq.block_ids[i])
            seq.cached_upto = i + 1

    def _live_width(self, nblocks: int) -> int:
        """Table width actually fed to the step: the longest live table
        padded to a power of two (capped at the provisioned maximum).
        Short-context workloads then gather a handful of blocks instead of
        the full ``max_blocks_per_seq`` slab — the compiled-shape count
        stays logarithmic and steady state still never retraces."""
        w = 1
        while w < nblocks:
            w *= 2
        return min(w, self.max_blocks_per_seq)

    # -- the lockstep iteration ----------------------------------------------
    def _prefill_one(self) -> int:
        """Advance ONE prefilling sequence by one chunk (round-robin) so a
        long prompt shares the step budget instead of owning it."""
        if not self._prefill_rr:
            return 0
        slot = self._prefill_rr.pop(0)
        seq = self._slots[slot]
        h = seq.handle
        bs = self.cache.block_size
        plen = len(h.prompt)
        start = seq.context_len
        n = min(self.prefill_chunk, plen - start)
        meta = self._pf_meta_np
        meta.fill(0)                       # 0 == null block == inactive
        for i in range(n):
            pos = start + i
            seq.context_len = pos          # _grow keys off context_len
            if not self._grow(seq):
                seq.context_len = start
                LOAD_SHED.inc(reason="kv_blocks")
                h.evicted = True
                self._retire(slot)
                return 0
            meta[0, i] = h.prompt[pos]
            meta[1, i] = pos
            meta[2, i] = pos + 1           # lens > 0 marks the row live
            meta[3, i] = seq.block_ids[pos // bs]
            meta[4, i] = pos % bs
        width = self._live_width(len(seq.block_ids))
        # copies, not the persistent mirrors: dispatch is asynchronous and
        # may read a host buffer in place after it returns, and a chunk
        # that does not end its prompt is not waited for — the next chunk
        # would rewrite ``meta`` and ``_grow`` the table under it
        self.cache.k, self.cache.v, nxt = self._prefill_fn(
            self.cache.k, self.cache.v,
            self._tables_np[slot, :width].copy(), meta.copy())
        seq.context_len = start + n
        KV_PREFILL_CHUNKS.inc()
        self._publish_full_blocks(seq)
        if seq.context_len == plen:        # prompt fully written: the last
            first = int(np.asarray(nxt)[n - 1])   # row's logits are token 0
            h.tokens.append(first)
            if not h._ttft_recorded:
                h._ttft_recorded = True
                TTFT_MS.observe((time.perf_counter() - h._t_submit) * 1e3)
            if len(h.tokens) >= h.max_new_tokens:
                self._retire(slot)
            else:
                self._state[slot] = _DECODE
        else:
            self._prefill_rr.append(slot)  # back of the round-robin queue
        return 1

    def step(self) -> int:
        """One prefill chunk (if any prompt is pending) + one decode token
        for every decoding sequence.  Returns prefill-chunks + decode rows
        advanced; 0 means idle."""
        advanced = self._prefill_one()

        meta = self._meta_np
        meta.fill(0)                       # 0 == null block == inactive
        bs = self.cache.block_size
        n_active = 0
        nblocks = 1
        for slot in range(self.max_seqs):
            if self._state[slot] != _DECODE:
                continue
            seq = self._slots[slot]
            h = seq.handle
            if not self._grow(seq):
                LOAD_SHED.inc(reason="kv_blocks")
                h.evicted = True
                self._retire(slot)
                continue
            pos = seq.context_len
            meta[0, slot] = h.tokens[-1]
            meta[1, slot] = pos
            meta[2, slot] = pos + 1        # lens > 0 marks the row live
            meta[3, slot] = seq.block_ids[pos // bs]
            meta[4, slot] = pos % bs
            if len(seq.block_ids) > nblocks:
                nblocks = len(seq.block_ids)
            n_active += 1
        if n_active:
            width = self._live_width(nblocks)
            self.cache.k, self.cache.v, nxt = self._decode_fn(
                self.cache.k, self.cache.v,
                np.ascontiguousarray(self._tables_np[:, :width]), meta)
            nxt = np.asarray(nxt)
            for slot in range(self.max_seqs):
                if meta[2, slot] == 0 or self._state[slot] != _DECODE:
                    continue
                seq = self._slots[slot]
                seq.context_len += 1
                seq.handle.tokens.append(int(nxt[slot]))
                if len(seq.handle.tokens) >= seq.handle.max_new_tokens:
                    self._retire(slot)
        return advanced + n_active

    def run_until_idle(self, max_steps: int = 100000) -> None:
        for _ in range(max_steps):
            if self.step() == 0:
                return
        raise RuntimeError(f"decode did not drain in {max_steps} steps")

    def decode(self, prompts: Sequence[Sequence[int]],
               max_new_tokens: int) -> List[List[int]]:
        """Convenience: decode every prompt, joining as capacity frees up,
        in prompt order (the ContinuousBatcher surface)."""
        handles: List[Optional[DecodeHandle]] = [None] * len(prompts)
        pending = list(range(len(prompts)))
        while pending or self.active_count:
            while pending:
                h = self.try_join(prompts[pending[0]], max_new_tokens)
                if h is None:
                    break
                handles[pending.pop(0)] = h
            if self.step() == 0 and pending:
                raise RuntimeError("pool cannot admit remaining prompts")
        return [h.tokens for h in handles]


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def dense_reference_decode(model: PagedToyLM, prompt: Sequence[int],
                           max_new_tokens: int) -> List[int]:
    """Straight-line dense greedy decode of ONE sequence — the parity
    oracle for the paged path (same math, no blocks, no batching)."""
    toks = [int(t) for t in prompt]
    out: List[int] = []
    k_rows: List[jax.Array] = []
    v_rows: List[jax.Array] = []
    sm = 1.0 / math.sqrt(model.hidden)
    last_logits = None
    for pos, t in enumerate(toks):
        q, k, v = model.qkv(jnp.asarray([t], jnp.int32),
                            jnp.asarray([pos], jnp.int32))
        k_rows.append(k)
        v_rows.append(v)
        ks = jnp.concatenate(k_rows, axis=0)
        vs = jnp.concatenate(v_rows, axis=0)
        p = jax.nn.softmax((q @ ks.T) * sm, axis=-1)
        last_logits = (p @ vs) @ model.wo
    cur = int(jnp.argmax(last_logits[0]))
    out.append(cur)
    pos = len(toks)
    while len(out) < max_new_tokens:
        q, k, v = model.qkv(jnp.asarray([cur], jnp.int32),
                            jnp.asarray([pos], jnp.int32))
        k_rows.append(k)
        v_rows.append(v)
        ks = jnp.concatenate(k_rows, axis=0)
        vs = jnp.concatenate(v_rows, axis=0)
        p = jax.nn.softmax((q @ ks.T) * sm, axis=-1)
        cur = int(jnp.argmax(((p @ vs) @ model.wo)[0]))
        out.append(cur)
        pos += 1
    return out
