"""Paged KV-cache manager for autoregressive (decode) fragments.

Each decode-capable stage pool owns ONE :class:`PagedKVCache`: a
preallocated arena of fixed-size token blocks, held on the device, that
books the KV capacity of every request resident in that pool's continuous
decode batch. The design is the vLLM paged-attention bookkeeping reduced
to what the serving path needs:

- **Block-granular alloc/free.** A free list over ``n_blocks`` blocks of
  ``block_tokens`` token slots each; sequences hold chains of blocks and
  release them the moment they finish, so a long-running batch never
  holds arena capacity for requests that already completed.
- **Cross-request prefix sharing.** Prompt blocks are indexed under a
  chained hash key rooted at the pool's ``reuse.fragment_signature`` —
  ``(sig, parent_key, block-token-tuple)`` — so two requests whose
  prompts share a block-aligned prefix (same model / partition point /
  SLO bucket) share the underlying KV blocks by refcount instead of
  recomputing prefill. The trailing *partial* prompt block is indexed
  too, which is what makes copy-on-write reachable: a sharer that
  decodes appends into a shared partial block and must COW it first.
- **Retention + LRU eviction.** On ``finish`` a sequence's prompt
  blocks stay allocated (refcount 0, indexed) as reuse candidates;
  allocation pressure evicts the least-recently-touched retained block
  before raising :class:`KVCacheOOM`. Eviction / hit / COW counters are
  surfaced in pool stats.

The arena holds prompt KV, for prefix sharing and the disaggregated
handoff, and books every stream's capacity in blocks; the pool's dense
per-slot decode cache holds resident streams' KV, so a decode step
writes no row here.

K and V are two device arrays stacked over layers — ``(block, slot,
layer, kv_head, head_dim)`` — at the dtype of the pool's dense decode
cache, so nothing widens or narrows them. Every data operation is one
jitted program of fixed shape: a prompt's private blocks written from the
B=1 prefill cache and a block copied for copy-on-write, both donating the
arena so it updates in place, and a sequence's prompt blocks gathered
back into the B=1 cache layout. Block tables are padded to
``max_seq_tokens`` with out-of-range entries, which the scatter drops and
the gather fills with zeros, so no program is traced per prompt length.
Block chains, refcounts, the prefix index, LRU and COW decisions stay on
the host. KV reaches the host only in :meth:`export_prefix`, the prefill
side of a disaggregated handoff.
"""
from __future__ import annotations

import functools
import hashlib
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.telemetry import NULL as NULL_TELEMETRY, phase


class KVCacheOOM(RuntimeError):
    """Block allocation failed: free list empty and nothing evictable."""


@dataclass
class _Block:
    idx: int
    ref: int = 0                  # active sequences using this block
    filled: int = 0               # token slots with resident KV
    tokens: tuple = ()            # token ids resident in this block
    key: Optional[tuple] = None   # prefix-index key when indexed
    tick: int = 0                 # last-touched stamp (LRU eviction)
    free: bool = True


@dataclass
class _Seq:
    rid: int
    sig: tuple
    blocks: list = field(default_factory=list)     # _Block chain, in order
    n_tokens: int = 0                              # resident tokens (total)
    prompt_len: int = 0
    n_shared: int = 0                              # prefix tokens reused
    n_shared_blocks: int = 0                       # ...in this many blocks
    prompt_keys: list = field(default_factory=list)  # chain keys per block


def _chunk(tokens: tuple, bt: int) -> list[tuple]:
    return [tokens[i:i + bt] for i in range(0, len(tokens), bt)]


def prompt_chain_keys(sig: tuple, tokens: tuple, bt: int) -> list[tuple]:
    """Chained prefix-index keys for a prompt, one per block. Full blocks
    key as ("B", parent, chunk); the trailing partial as ("P", parent,
    chunk) so a partial block only matches a request whose prompt ends
    with the identical partial chunk."""
    keys, prev = [], ("root", sig)
    for chunk in _chunk(tokens, bt):
        kind = "B" if len(chunk) == bt else "P"
        key = (kind, prev, chunk)
        keys.append(key)
        prev = key
    return keys


def key_digest(key: tuple) -> int:
    """Stable 64-bit digest of one prefix-index key. ``repr`` of the
    chain key is deterministic (ints/strings/tuples only — never the
    salted builtin ``hash``), so digests compare equal across processes
    and front-ends."""
    h = hashlib.blake2b(repr(key).encode(), digest_size=8).digest()
    return int.from_bytes(h, "big")


def prefix_digest(sig: tuple, tokens, block_tokens: int, *,
                  max_chunks: int = 4) -> tuple:
    """Compact routing digest of one prompt: hashes of its first
    ``max_chunks`` chain keys under ``sig``. A request whose digest
    overlaps a front-end's residency digest has prompt-prefix KV blocks
    already live behind that front-end — the router's affinity signal."""
    toks = tuple(int(t) for t in np.asarray(tokens).reshape(-1))
    if not toks:
        return ()
    keys = prompt_chain_keys(sig, toks, block_tokens)[:max(max_chunks, 1)]
    return tuple(key_digest(k) for k in keys)


# The arena's data operations. K and V are donated, so each updates in
# place; the block table and positions are traced, so one program serves
# every prompt length. Out-of-range block indices (``n_blocks``) pad the
# tables: a scatter drops them, a gather reads zeros there.

def _cache_blocks(x, nb: int, bt: int, dtype):
    """Row 0 of a B=1 cache array (L, 1, S, KV, hd), S <= nb * bt, as
    ``nb`` whole blocks in the arena's layout (nb, bt, L, KV, hd)."""
    x = jnp.pad(x[:, 0], ((0, 0), (0, nb * bt - x.shape[2]), (0, 0), (0, 0)))
    L, _, KV, hd = x.shape
    return x.reshape(L, nb, bt, KV, hd).transpose(1, 2, 0, 3, 4).astype(dtype)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _write_blocks(ak, av, k, v, table):
    """Cache block j of ``k``, ``v`` (positions j*bt onwards) into arena
    block ``table[j]``."""
    nb, bt = table.shape[0], ak.shape[1]
    return (ak.at[table].set(_cache_blocks(k, nb, bt, ak.dtype), mode="drop"),
            av.at[table].set(_cache_blocks(v, nb, bt, av.dtype), mode="drop"))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _copy_block(ak, av, src, dst):
    return ak.at[dst].set(ak[src]), av.at[dst].set(av[src])


@functools.partial(jax.jit, static_argnames=("width",))
def _gather_blocks(ak, av, table, n, *, width: int):
    """Arena blocks ``table`` as positions 0..width of a B=1 cache array
    (L, 1, width, KV, hd); positions from ``n`` on read zero."""
    live = (jnp.arange(width) < n)[:, None, None, None]

    def one(a):
        x = a.at[table].get(mode="fill", fill_value=0)
        x = x.reshape((-1,) + a.shape[2:])[:width]
        return jnp.where(live, x, 0).transpose(1, 0, 2, 3)[:, None]

    return one(ak), one(av)


class PagedKVCache:
    """Block-granular KV arena with prefix sharing and LRU retention."""

    def __init__(self, n_blocks: int, block_tokens: int, *,
                 n_layers: int, n_kv_heads: int, head_dim: int,
                 max_seq_tokens: Optional[int] = None, dtype=jnp.float32,
                 telemetry=None):
        """``max_seq_tokens`` (default: the whole arena) bounds one
        sequence and sets the width of every block table, and of the B=1
        cache arrays :meth:`write_prompt_kv` takes and :meth:`gather`
        returns; ``dtype`` is that cache's."""
        if n_blocks <= 0 or block_tokens <= 0:
            raise ValueError("n_blocks and block_tokens must be positive")
        self.n_blocks = n_blocks
        self.block_tokens = block_tokens
        self.max_seq_tokens = int(max_seq_tokens or n_blocks * block_tokens)
        self._table_len = -(-self.max_seq_tokens // block_tokens)
        self.dtype = jnp.dtype(dtype)
        shape = (n_blocks, block_tokens, n_layers, n_kv_heads, head_dim)
        self._k = jnp.zeros(shape, self.dtype)
        self._v = jnp.zeros(shape, self.dtype)
        self._blocks = [_Block(i) for i in range(n_blocks)]
        self._free: list[int] = list(range(n_blocks - 1, -1, -1))
        self._index: dict[tuple, _Block] = {}
        self._seqs: dict[int, _Seq] = {}
        self._tick = 0
        self.counters = {"allocs": 0, "frees": 0, "evictions": 0,
                         "prefix_hits": 0, "prefix_tokens_reused": 0,
                         "cow_copies": 0, "oom": 0,
                         "handoff_blocks_in": 0, "handoff_tokens_in": 0,
                         "handoff_reused": 0}
        tel = telemetry if telemetry is not None else NULL_TELEMETRY
        self._tel_on = tel.enabled      # gates the O(n_blocks) util scan
        self._m_util = tel.gauge("kv/util_frac")
        self._m_evictions = tel.counter("kv/evictions")
        self._m_cow = tel.counter("kv/cow_copies")
        self._m_oom = tel.counter("kv/oom")

    # ----------------------------------------------------------- internals
    def _touch(self, blk: _Block) -> None:
        self._tick += 1
        blk.tick = self._tick

    def _alloc_block(self) -> _Block:
        if self._free:
            blk = self._blocks[self._free.pop()]
        else:
            blk = self._evict_lru()
        if not blk.free:
            raise RuntimeError(f"allocator invariant: block {blk.idx} "
                               "handed out while not free")
        blk.free = False
        blk.ref = 1
        blk.filled = 0
        blk.tokens = ()
        blk.key = None
        self._touch(blk)
        self.counters["allocs"] += 1
        if self._tel_on:
            self._m_util.set(self.util_frac())
        return blk

    def _evict_lru(self) -> _Block:
        """Reclaim the least-recently-touched retained block (ref 0,
        indexed). Raises KVCacheOOM when every block is actively held."""
        victim = None
        for blk in self._blocks:
            if blk.free or blk.ref > 0:
                continue
            if victim is None or blk.tick < victim.tick:
                victim = blk
        if victim is None:
            self.counters["oom"] += 1
            self._m_oom.inc()
            raise KVCacheOOM(
                f"KV arena exhausted: {self.n_blocks} blocks all actively "
                "referenced (nothing retained to evict)")
        if victim.key is not None:
            self._index.pop(victim.key, None)
        self.counters["evictions"] += 1
        self._m_evictions.inc()
        victim.free = True          # immediately re-handed by _alloc_block
        return victim

    def _free_block(self, blk: _Block) -> None:
        if blk.free:
            raise RuntimeError(f"double free of KV block {blk.idx}")
        if blk.key is not None:
            self._index.pop(blk.key, None)
            blk.key = None
        blk.free = True
        blk.ref = 0
        blk.filled = 0
        blk.tokens = ()
        self._free.append(blk.idx)
        self.counters["frees"] += 1
        if self._tel_on:
            self._m_util.set(self.util_frac())

    def _drop_ref(self, blk: _Block) -> None:
        """Release one sequence's hold. At ref 0 an INDEXED block stays
        allocated as a retained reuse candidate (evictable under
        pressure); anything unindexed frees. Indexed blocks survive even
        an abort-path drop — a sharer releasing early must not destroy
        the donor's retained prefix it merely borrowed."""
        if blk.free:
            raise RuntimeError(f"release of already-freed KV block {blk.idx}")
        blk.ref -= 1
        if blk.ref < 0:
            raise RuntimeError(f"refcount underflow on KV block {blk.idx}")
        if blk.ref == 0 and blk.key is None:
            self._free_block(blk)

    # --------------------------------------------------------------- API
    def begin(self, rid: int, sig: tuple, prompt_tokens) -> int:
        """Admit a sequence: share the longest indexed prefix, allocate
        private blocks for the remainder. Returns the number of prompt
        tokens whose KV is already resident (the caller gathers those
        and prefills only the suffix). KV for the private blocks must be
        written via :meth:`write_prompt_kv` before any gather."""
        if rid in self._seqs:
            raise ValueError(f"sequence {rid} already admitted")
        tokens = tuple(int(t) for t in np.asarray(prompt_tokens).reshape(-1))
        if not tokens:
            raise ValueError("empty prompt")
        if len(tokens) > self.max_seq_tokens:
            raise ValueError(f"prompt of {len(tokens)} tokens exceeds "
                             f"max_seq_tokens={self.max_seq_tokens}")
        seq = _Seq(rid=rid, sig=sig, prompt_len=len(tokens))
        seq.prompt_keys = prompt_chain_keys(sig, tokens, self.block_tokens)
        chunks = _chunk(tokens, self.block_tokens)
        shared = 0
        for key, chunk in zip(seq.prompt_keys, chunks):
            blk = self._index.get(key)
            if blk is None or blk.tokens != chunk:
                break
            blk.ref += 1
            self._touch(blk)
            seq.blocks.append(blk)
            shared += blk.filled
        seq.n_shared_blocks = len(seq.blocks)
        for chunk in chunks[len(seq.blocks):]:
            try:
                blk = self._alloc_block()
            except KVCacheOOM:
                self._unwind(seq)
                raise
            blk.tokens = chunk
            blk.filled = len(chunk)
            seq.blocks.append(blk)
        seq.n_shared = shared
        seq.n_tokens = len(tokens)
        if shared:
            self.counters["prefix_hits"] += 1
            self.counters["prefix_tokens_reused"] += shared
        self._seqs[rid] = seq
        return shared

    def _unwind(self, seq: _Seq) -> None:
        """Roll back a partially-admitted sequence (OOM mid-begin)."""
        for blk in seq.blocks:
            self._drop_ref(blk)

    def _table(self, blocks, lo: int = 0) -> np.ndarray:
        """Block table of a chain, padded to the arena's table width with
        the out-of-range index ``n_blocks``; entries before ``lo`` pad."""
        table = np.full(self._table_len, self.n_blocks, np.int32)
        for j in range(lo, len(blocks)):
            table[j] = blocks[j].idx
        return table

    def write_prompt_kv(self, rid: int, k, v) -> None:
        """Write the KV of the prompt's private (non-shared) blocks from
        the B=1 cache arrays ``k``, ``v`` (L, 1, S, KV, hd) that hold the
        prompt at positions 0..prompt_len. Whole blocks are written;
        positions past the prompt in its last block are never read.
        Shared blocks are never rewritten."""
        seq = self._seqs[rid]
        S = k.shape[2]
        if not seq.prompt_len <= S <= self._table_len * self.block_tokens:
            raise ValueError(f"cache of {S} positions for a prompt of "
                             f"{seq.prompt_len} tokens in an arena of "
                             f"{self.max_seq_tokens}-token sequences")
        with phase("kv/write_prompt"):
            private = seq.blocks[seq.n_shared_blocks:]
            if not private:
                return
            for blk in private:
                self._touch(blk)
            self._k, self._v = _write_blocks(
                self._k, self._v, k, v,
                self._table(seq.blocks, seq.n_shared_blocks))

    def _writable_last(self, seq: _Seq) -> _Block:
        """The sequence's last block, copy-on-write'd if shared. A block
        is privately writable only when this sequence is its sole active
        user AND it is not a retained index entry other requests may
        still match."""
        blk = seq.blocks[-1]
        if blk.ref == 1 and blk.key is None:
            return blk
        fresh = self._alloc_block()
        fresh.tokens = blk.tokens
        fresh.filled = blk.filled
        self._k, self._v = _copy_block(self._k, self._v, np.int32(blk.idx),
                                       np.int32(fresh.idx))
        self._drop_ref(blk)
        seq.blocks[-1] = fresh
        self.counters["cow_copies"] += 1
        self._m_cow.inc()
        return fresh

    def append(self, rids: list, tokens) -> list:
        """Book one more token for each stream of a decode step:
        ``tokens[b]`` for stream ``rids[b]``, nothing where ``rids[b]`` is
        None. A stream allocates at a block boundary and copies a shared
        partial block (COW) first; its KV stays in the pool's dense cache.
        Returns the streams whose allocation found the arena full
        (:class:`KVCacheOOM`): nothing of theirs changed, for the caller
        to release."""
        bt = self.block_tokens
        with phase("kv/append"):
            oom = []
            for rid, token in zip(rids, tokens):
                if rid is None:
                    continue
                seq = self._seqs[rid]
                try:
                    if seq.n_tokens % bt == 0:         # boundary: new block
                        blk = self._alloc_block()
                        seq.blocks.append(blk)
                    else:
                        blk = self._writable_last(seq)
                except KVCacheOOM:
                    oom.append(rid)
                    continue
                blk.tokens = blk.tokens + (int(token),)
                blk.filled += 1
                seq.n_tokens += 1
                self._touch(blk)
            return oom

    def _gather(self, blocks, n: int):
        nb = -(-n // self.block_tokens)
        return _gather_blocks(self._k, self._v, self._table(blocks[:nb]),
                              np.int32(n), width=self.max_seq_tokens)

    def gather(self, rid: int, n: Optional[int] = None):
        """KV of the sequence's first ``n`` prompt tokens (default: the
        whole prompt) as B=1 cache arrays on the device, (L, 1,
        max_seq_tokens, KV, hd) at the arena's dtype; positions from ``n``
        on read zero. The arena holds no decode row, so an ``n`` past the
        prompt raises."""
        with phase("kv/gather"):
            seq = self._seqs[rid]
            n = seq.prompt_len if n is None else n
            if n > seq.prompt_len:
                raise ValueError(f"sequence {rid}: asked {n} tokens, the "
                                 f"arena holds its {seq.prompt_len} prompt "
                                 "tokens")
            return self._gather(seq.blocks, n)

    def finish(self, rid: int, *, retain: bool = True) -> None:
        """Complete a sequence. Prompt blocks whose content still matches
        the admission-time chain become retained reuse candidates
        (indexed, refcount 0, evictable); everything else frees as its
        refcount drops."""
        seq = self._seqs.pop(rid)
        chunks = _chunk(self._prompt_tokens(seq), self.block_tokens)
        for i, blk in enumerate(seq.blocks):
            indexable = (retain and i < len(seq.prompt_keys)
                         and blk.tokens == chunks[i] and blk.key is None
                         and seq.prompt_keys[i] not in self._index)
            if indexable:
                blk.key = seq.prompt_keys[i]
                self._index[blk.key] = blk
                self._touch(blk)
            self._drop_ref(blk)

    def _prompt_tokens(self, seq: _Seq) -> tuple:
        toks: list[int] = []
        for blk in seq.blocks:
            if len(toks) >= seq.prompt_len:
                break
            toks.extend(blk.tokens[:seq.prompt_len - len(toks)])
        return tuple(toks)

    def release(self, rid: int) -> None:
        """Abort path: drop the sequence without retaining anything new."""
        self.finish(rid, retain=False)

    # ------------------------------------------------- cross-arena handoff
    def export_prefix(self, rid: int) -> dict:
        """Serialize a resident sequence's prompt blocks for a cross-pool
        handoff (prefill pool -> decode pool over the transport). The
        payload carries the signature and the per-block token chunks —
        everything :func:`prompt_chain_keys` needs — so the importing
        arena indexes the blocks under the *identical* chain keys and
        cross-request prefix sharing survives the hop. The blocks come
        down from the device in one copy at the arena's dtype, which
        ``d2h_bytes`` counts, and are widened to float32 host arrays: the
        exporting arena may evict or free them the moment the frame is
        on the wire."""
        seq = self._seqs[rid]
        chunks = _chunk(self._prompt_tokens(seq), self.block_tokens)
        keep = []
        for i, blk in enumerate(seq.blocks[:len(chunks)]):
            if blk.tokens != chunks[i]:
                break                 # diverged (post-prompt append): stop
            keep.append(blk)
        n = sum(blk.filled for blk in keep)
        blocks, nbytes = [], 0
        if keep:
            hk, hv = (np.asarray(x) for x in self._gather(seq.blocks, n))
            nbytes = hk.nbytes + hv.nbytes
            # (n, L, KV, hd): position p of the prompt is row p
            rk, rv = (h[:, 0, :n].astype(np.float32).transpose(1, 0, 2, 3)
                      for h in (hk, hv))
            lo = 0
            for blk in keep:
                blocks.append({"tokens": [int(t) for t in blk.tokens],
                               "filled": int(blk.filled),
                               "k": rk[lo:lo + blk.filled],
                               "v": rv[lo:lo + blk.filled]})
                lo += blk.filled
        return {"sig": seq.sig, "block_tokens": self.block_tokens,
                "prompt_len": seq.prompt_len, "blocks": blocks,
                "d2h_bytes": nbytes}

    def import_prefix(self, sig: tuple, blocks: list) -> dict:
        """Seed the prefix index with exported prompt blocks. Each block
        lands as a retained reuse candidate (indexed, refcount 0,
        evictable) under the same chain key the exporter held, so the
        next :meth:`begin` for this prompt shares them like any locally
        retained prefix — and so do OTHER requests sharing a block-
        aligned prefix. Chunks already indexed here are skipped (the
        affinity-routed case); an OOM mid-import keeps the contiguous
        prefix imported so far and stops — ``begin`` recomputes the tail,
        degraded, never wrong. The new blocks go up to the device in one
        write, cast to the arena's dtype (exact for a payload exported
        from an arena of that dtype). Returns counters for the caller's
        stats."""
        toks = tuple(int(t) for b in blocks for t in b["tokens"])
        keys = prompt_chain_keys(sig, toks, self.block_tokens)
        imported = reused = tokens_in = 0
        pinned: list = []             # chain blocks held until import ends
        bt = self.block_tokens
        table = np.full(self._table_len, self.n_blocks, np.int32)
        hk = hv = None                # B=1 cache arrays of the new blocks
        for j, (key, b) in enumerate(zip(keys[:self._table_len], blocks)):
            chunk = tuple(int(t) for t in b["tokens"])
            have = self._index.get(key)
            if have is not None and have.tokens == chunk:
                self._touch(have)     # refresh LRU: it is hot again
                have.ref += 1         # pin: a later alloc must not evict
                pinned.append(have)   # the chain out from under itself
                reused += 1
                continue
            try:
                blk = self._alloc_block()
            except KVCacheOOM:
                break                 # chain keys need contiguity: stop
            n = min(int(b["filled"]), bt)
            if hk is None:
                L, KV, hd = self._k.shape[2:]
                hk, hv = (np.zeros((L, 1, self._table_len * bt, KV, hd),
                                   self.dtype) for _ in range(2))
            for h, x in ((hk, b["k"]), (hv, b["v"])):
                h[:, 0, j * bt:j * bt + n] = np.asarray(x)[:n].transpose(
                    1, 0, 2, 3)
            table[j] = blk.idx
            blk.tokens = chunk
            blk.filled = n
            blk.ref = 1               # pinned while the import runs
            blk.key = key
            self._index[key] = blk
            pinned.append(blk)
            imported += 1
            tokens_in += n
        if imported:
            self._k, self._v = _write_blocks(self._k, self._v, hk, hv, table)
        for blk in pinned:
            blk.ref -= 1              # land retained (ref 0, evictable)
        self.counters["handoff_blocks_in"] += imported
        self.counters["handoff_tokens_in"] += tokens_in
        self.counters["handoff_reused"] += reused
        return {"imported": imported, "reused": reused,
                "tokens_in": tokens_in}

    # ------------------------------------------------------------- stats
    @property
    def n_free(self) -> int:
        return len(self._free)

    def n_resident(self, rid: int) -> int:
        return self._seqs[rid].n_tokens

    def capacity_tokens(self) -> int:
        """Token slots obtainable without OOM: free blocks plus evictable
        retained blocks."""
        evictable = sum(1 for b in self._blocks if not b.free and b.ref == 0)
        return (len(self._free) + evictable) * self.block_tokens

    def has_room(self, n_tokens: int, n_resident: int = 0) -> bool:
        """Admission check: can ``n_tokens`` more tokens be resident,
        given ``n_resident`` already-held tokens round up to blocks."""
        bt = self.block_tokens
        need = (n_resident + n_tokens + bt - 1) // bt \
            - (n_resident + bt - 1) // bt
        evictable = sum(1 for b in self._blocks if not b.free and b.ref == 0)
        return need <= len(self._free) + evictable

    def util_frac(self) -> float:
        """Used token slots / allocated token slots. 1.0 when nothing is
        allocated (an empty arena wastes nothing)."""
        alloc = [b for b in self._blocks if not b.free]
        if not alloc:
            return 1.0
        return sum(b.filled for b in alloc) / (len(alloc) * self.block_tokens)

    def residency_digest(self, cap: int = 512) -> tuple:
        """Compact digest of the prefix index — :func:`key_digest` of the
        most recently touched indexed blocks' keys, newest first. This is
        what a front-end exports into the router's affinity signal: a
        request whose :func:`prefix_digest` overlaps it can reuse resident
        prompt KV here instead of re-prefixing on a cold front-end."""
        blocks = [b for b in self._blocks if b.key is not None and not b.free]
        blocks.sort(key=lambda b: -b.tick)
        return tuple(key_digest(b.key) for b in blocks[:max(int(cap), 0)])

    def stats(self) -> dict:
        return {**self.counters,
                "n_blocks": self.n_blocks,
                "block_tokens": self.block_tokens,
                "free_blocks": len(self._free),
                "active_seqs": len(self._seqs),
                "util_frac": round(self.util_frac(), 4)}
