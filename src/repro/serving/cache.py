"""Serving caches: slot-paged int8 KV + absmax scales + packed LOP features.

The KV cache follows the paper's memory layout insight: exact keys/values in
int8 (absmax barrier), plus the 4-bit (sgn‖LO) *feature cache* the LOP screen
reads instead of the exact keys — the screen touches M·d/2 bytes while exact
attention touches only the K selected candidate blocks.

Latent attention (``cfg.mla``) caches one int8 row c ‖ k_rope per token
(``k``, one lane shared by every head) with its absmax scale and its
packed features — the same leaves as a K cache, and no V: the values
are the row's first ``kv_lora_rank`` columns. A model with leading
dense layers (``first_k_dense``) keeps their cache in a stack of its own,
``dense_layers``, beside ``layers``; every pool op walks both.

Capacity is block-aligned (``lop_block``) so candidate fetches stay
contiguous. Recurrent families cache their state instead ("KV cache of
seq_len" = recurrent state for SSM — DESIGN.md §6).

Slot-paged pool (continuous batching)
-------------------------------------
``init_cache_pool`` allocates the same tree for ``n_slots`` persistent
*decode lanes* plus a per-lane ``active`` mask. The lifecycle managed by
:mod:`repro.serving.scheduler` is::

    admit    a queued request once a lane is free,
    prefill  it — chunked families interleave one fixed-shape chunk per
             serve cycle (extract_slot → prefill_chunk → partial
             insert_slot with ``active=False``); the legacy path runs the
             whole prompt at once (length-bucketed compile) into a
             batch-1 cache,
    insert   that cache into the lane (``insert_slot``, one
             ``dynamic_update_slice`` per leaf) while the other lanes
             keep decoding; the final chunk's insert activates the lane
             and its logits seed the first token through the per-request
             sampler (:mod:`repro.serving.sampling`),
    decode   all active lanes together; inactive lanes are masked out of
             the LOP screen, block top-K and cache writes,
    evict    the lane on EOS/max-len (``evict_slot``) — the lane's bytes go
             stale but every read is masked by per-slot ``lengths``, so the
             next occupant sees a logically fresh lane.

Stale bytes above a lane's ``lengths`` are harmless by construction: the
LOP screen masks them to INT32_MIN before block reduction and exact
attention masks them to −∞ before the softmax, which is also why
evict→insert reuse is bit-identical to a zero-initialised lane.
``evict_slot`` additionally zeroes the lane's packed LOP feature rows so
a later prefix-clone lands in a lane bit-identical to a fresh pool.

The pool also carries the per-lane *sampling state* (``seed``,
``sample_step``) as cache leaves, so the fused decode+sample step reads
its PRNG schedule straight from the pool — a cloned or migrated lane
samples correctly with no host round-trip (DESIGN.md §Prefix-caching).

Prefix caching (shared prompts cost one prefill)
------------------------------------------------
:class:`PrefixStore` interns computed prefill state keyed by token-block
hash chains: block ``k`` of a prompt is keyed by
``blake2b(parent_key ‖ tokens[k·B:(k+1)·B])``, so equal prompt prefixes
— and only equal prefixes — share a chain of nodes, each holding that
block's *cache pages* (the K/V **and** packed LOP feature rows sliced
from a batch-1 prefill at the block's token range). ``bulk_insert``
clones one assembled prefix into many pool lanes in a single scatter;
the scheduler then resumes chunked prefill from the cached block
boundary via the existing bitwise ``(start, kv_len)`` chunk-carry
contract (DESIGN.md §Prefix-caching).
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving import faults as _faults


def round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def attn_cache_zeros(cfg, batch: int, capacity: int):
    if cfg.mla:
        d = cfg.latent_dim
        return {
            "k": jnp.zeros((batch, 1, capacity, d), jnp.int8),
            "k_scale": jnp.zeros((batch, 1, capacity), jnp.float32),
            "feat": jnp.zeros((batch, 1, capacity, d // 2), jnp.uint8),
        }
    hkv, dh = cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((batch, hkv, capacity, dh), jnp.int8),
        "v": jnp.zeros((batch, hkv, capacity, dh), jnp.int8),
        "k_scale": jnp.zeros((batch, hkv, capacity), jnp.float32),
        "v_scale": jnp.zeros((batch, hkv, capacity), jnp.float32),
        "feat": jnp.zeros((batch, hkv, capacity, dh // 2), jnp.uint8),
    }


def _stack(tree, n: int):
    return jax.tree.map(
        lambda a: jnp.zeros((n, *a.shape), a.dtype), tree)


def init_cache(cfg, batch: int, max_len: int, *, align: int | None = None):
    """Zero cache sized for ``max_len`` tokens (+1 block of decode slack).

    ``align`` (default lop_block) also aligns capacity to the SP shard
    count × block so every M-shard is block-aligned.
    """
    cap = round_up(max_len + 1, align or cfg.lop_block)
    cache = {"lengths": jnp.zeros((batch,), jnp.int32)}

    if cfg.family in ("dense", "moe", "vlm"):
        layer = attn_cache_zeros(cfg, batch, cap)
        if cfg.first_k_dense:
            cache["dense_layers"] = _stack(layer, cfg.first_k_dense)
        cache["layers"] = _stack(layer, cfg.n_layers - cfg.first_k_dense)
    elif cfg.family == "hybrid":
        n_sb = cfg.n_layers // cfg.attn_every
        n_mamba = cfg.attn_every - 1
        cache["blocks"] = {
            "attn": _stack(attn_cache_zeros(cfg, batch, cap), n_sb),
            "mamba": {
                "ssm": jnp.zeros((n_sb, n_mamba, batch, cfg.d_inner,
                                  cfg.mamba_d_state), jnp.float32),
                "conv": jnp.zeros((n_sb, n_mamba, batch, cfg.mamba_conv - 1,
                                   cfg.d_inner), jnp.float32),
            },
        }
    elif cfg.family == "ssm":
        cache["layers"] = {
            "wkv": jnp.zeros((cfg.n_layers, batch, cfg.n_heads, cfg.hd,
                              cfg.hd), jnp.float32),
            "x_tm": jnp.zeros((cfg.n_layers, batch, 1, cfg.d_model),
                              jnp.float32),
            "x_cm": jnp.zeros((cfg.n_layers, batch, 1, cfg.d_model),
                              jnp.float32),
        }
    elif cfg.family == "encdec":
        cross_cap = round_up(cfg.cross_ctx, align or cfg.lop_block)
        cache["layers"] = _stack(attn_cache_zeros(cfg, batch, cap),
                                 cfg.n_layers)
        cache["cross"] = _stack(attn_cache_zeros(cfg, batch, cross_cap),
                                cfg.n_layers)
        cache["cross_len"] = jnp.zeros((batch,), jnp.int32)
    else:
        raise ValueError(cfg.family)
    return cache


def _leaf_spec(path, *, batch_axes="dp", seq_axes="sp"):
    """Logical axes of one cache leaf, *excluding* stacked leading dims.

    Every spec starts at the batch/slot axis, so the slot axis index of a
    leaf is ``leaf.ndim - len(_leaf_spec(path))`` (used by ``insert_slot``).
    """
    name = path[-1]
    if name in ("k", "v", "feat"):
        return (batch_axes, None, seq_axes, None)
    if name in ("k_scale", "v_scale"):
        return (batch_axes, None, seq_axes)
    if name in ("lengths", "cross_len", "active", "seed", "sample_step"):
        return (None,)
    if name == "ssm":
        return (batch_axes, "tp", None)
    if name == "conv":
        return (batch_axes, None, "tp")
    if name == "wkv":
        return (batch_axes, "tp", None, None)
    if name in ("x_tm", "x_cm"):
        return (batch_axes, None, None)
    raise KeyError(path)


def cache_pspecs(cfg, cache, *, batch_axes="dp", seq_axes="sp"):
    """Logical-axis tree for the cache (M sequence-sharded, batch over dp).

    Attention caches shard the token axis over the model axis (SP) — the
    quota-sharded LOP selection in :mod:`repro.distributed.sp_decode` works
    per M-shard. Recurrent state shards its inner dim over the model axis.
    The per-slot ``lengths``/``active`` vectors stay replicated.
    """
    def walk(path, node):
        if isinstance(node, dict):
            return {k: walk(path + (k,), v) for k, v in node.items()}
        spec = _leaf_spec(path, batch_axes=batch_axes, seq_axes=seq_axes)
        # stacked leading dims (layers / superblocks / per-block sublayers)
        extra = node.ndim - len(spec)
        return (None,) * extra + spec

    return walk((), cache)


# ---------------------------------------------------------------------------
# Slot-paged pool ops (continuous batching)
# ---------------------------------------------------------------------------

def slot_axis(path, leaf) -> int:
    """Index of the slot (batch) axis in a cache leaf at ``path``."""
    return leaf.ndim - len(_leaf_spec(path))


def seq_axis(path, leaf) -> int:
    """Index of the token (sequence) axis in a cache leaf at ``path``.

    Defined only for positional caches (K/V/scales/features); recurrent
    state has no token axis, which is also why prefix pages are undefined
    for it.
    """
    spec = _leaf_spec(path)
    if "sp" not in spec:
        raise ValueError(f"cache leaf {path} has no token axis (recurrent "
                         f"state) — prefix pages are undefined for it")
    return leaf.ndim - len(spec) + spec.index("sp")


def init_cache_pool(cfg, n_slots: int, max_len: int, *,
                    align: int | None = None):
    """Slot-paged pool: ``n_slots`` persistent decode lanes, all inactive.

    Identical tree to :func:`init_cache` (so ``serve_step`` runs on it
    unchanged) plus a per-lane ``active`` mask that the engine threads
    through the LOP screen, block top-K and cache writes, and the
    per-lane sampling state (``seed``, ``sample_step``) the fused
    decode+sample step reads in-graph — the PRNG schedule travels with
    the lane, so clones/migrations need no host round-trip to sample.
    """
    pool = init_cache(cfg, n_slots, max_len, align=align)
    pool["active"] = jnp.zeros((n_slots,), jnp.bool_)
    pool["seed"] = jnp.zeros((n_slots,), jnp.int32)
    pool["sample_step"] = jnp.zeros((n_slots,), jnp.int32)
    return pool


def pool_capacity(pool) -> int:
    """Token capacity M of the pool's attention lanes (0 if attention-free)."""
    caps = []

    def walk(path, node):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(path + (k,), v)
        elif path[-1] == "k" and "cross" not in path:
            spec = _leaf_spec(path)
            caps.append(node.shape[node.ndim - len(spec) + 2])

    walk((), pool)
    return caps[0] if caps else 0


def insert_slot(pool, slot, req_cache, active=True):
    """Write a single-request (batch-1) prefill cache into lane ``slot``.

    One ``dynamic_update_slice`` per leaf at that leaf's slot axis — the
    other lanes are untouched, so insertion composes with donated buffers
    in a jit'd decode loop. ``slot`` may be a traced scalar (one compile
    serves every lane). The request cache's token capacity may be smaller
    than the pool's; positions above it go stale and are masked by
    ``lengths``.

    ``active`` (static bool or traced scalar) is the *partial-insert*
    switch for chunked prefill: a mid-prefill lane is written back with
    ``active=False`` after every chunk — its K/V for [0, lengths) are
    real, but the decode step must not advance it — and the final chunk
    flips it live. The scheduler keeps such a lane out of its free-lane
    deque (note: :func:`free_slots` reports by ``active`` alone and does
    NOT know about reservations — DESIGN.md §Chunked-prefill).
    """
    def walk(path, dst, src):
        if isinstance(dst, dict):
            return {k: walk(path + (k,), dst[k], src[k]) if k in src
                    else dst[k] for k in dst}
        ax = slot_axis(path, dst)
        start = (0,) * ax + (slot,) + (0,) * (dst.ndim - ax - 1)
        return jax.lax.dynamic_update_slice(dst, src, start)

    new = walk((), {k: v for k, v in pool.items() if k != "active"},
               req_cache)
    new["active"] = pool["active"].at[slot].set(active)
    return new


def extract_slot(pool, slot):
    """Batch-1 view of lane ``slot`` — the inverse of :func:`insert_slot`.

    One ``dynamic_slice`` per leaf (``slot`` may be traced). Chunked
    prefill round-trips extract → ``prefill_chunk`` → partial
    ``insert_slot`` once per chunk, so the in-flight prompt's K/V lives
    in the pool between chunks rather than in host-side side state.
    """
    def walk(path, node):
        if isinstance(node, dict):
            return {k: walk(path + (k,), v) for k, v in node.items()
                    if k != "active"}
        ax = slot_axis(path, node)
        start = (0,) * ax + (slot,) + (0,) * (node.ndim - ax - 1)
        sizes = node.shape[:ax] + (1,) + node.shape[ax + 1:]
        return jax.lax.dynamic_slice(node, start, sizes)

    return walk((), pool)


def bulk_insert(pool, slots, req_cache, active=True):
    """Clone ONE batch-1 cache into MANY lanes — one scatter per leaf.

    ``slots`` is an int32 ``[N]`` vector of distinct lane indices; the
    size-1 slot axis of ``req_cache`` broadcasts across them, so a shared
    prefix computed once lands in every hit lane of an admit batch in a
    single dispatch (K/V pages AND the packed LOP feature rows — the
    sparse screen stays consistent with the exact keys it summarizes).
    Leaves smaller than the pool's along any non-slot axis (a prefix
    cache's token capacity is its own block-aligned length) write their
    own extent; positions above it keep the lane's previous bytes, which
    are zero for feature rows (``evict_slot``) and stale-masked
    everywhere else. Dst keys missing from ``req_cache`` (``seed``,
    ``sample_step``, per-lane vectors the prefix does not carry) keep
    their pool values, like :func:`insert_slot`.

    ``active`` follows :func:`insert_slot`'s partial-insert contract:
    prefix clones land with ``active=False`` — the lanes are mid-prefill
    reservations that resume chunked prefill from the cached boundary.
    """
    def walk(path, dst, src):
        if isinstance(dst, dict):
            return {k: walk(path + (k,), dst[k], src[k]) if k in src
                    else dst[k] for k in dst}
        ax = slot_axis(path, dst)
        idx = tuple(
            slots if i == ax
            else slice(0, src.shape[i]) if src.shape[i] != dst.shape[i]
            else slice(None)
            for i in range(dst.ndim))
        return dst.at[idx].set(src, unique_indices=True)

    new = walk((), {k: v for k, v in pool.items() if k != "active"},
               req_cache)
    new["active"] = pool["active"].at[slots].set(active)
    return new


def evict_slot(pool, slot):
    """Retire lane ``slot``: mark inactive, zero its length AND its packed
    LOP feature rows.

    The K/V bytes are left stale — every consumer masks by
    ``lengths``/``active``, and the next ``insert_slot`` overwrites them.
    The 4-bit feature rows are zeroed because the LOP screen reads them
    *before* its length mask folds the scores away: the masking makes a
    previous occupant's ghost features logically invisible, but zeroing
    restores the lane to its pool-init bit pattern, so a later
    prefix-clone (which writes only the prefix's rows) screens against
    exactly what a fresh pool would.
    """
    def walk(path, node):
        if isinstance(node, dict):
            return {k: walk(path + (k,), v) for k, v in node.items()}
        if path[-1] != "feat":
            return node
        ax = slot_axis(path, node)
        return node.at[(slice(None),) * ax + (slot,)].set(0)

    pool = walk((), pool)
    pool["active"] = pool["active"].at[slot].set(False)
    pool["lengths"] = pool["lengths"].at[slot].set(0)
    return pool


# positional (per-token) attention-cache leaves — the rows a speculative
# write touches and a rollback must rewind
_POSITIONAL_KEYS = ("k", "v", "k_scale", "v_scale", "feat")


def rollback_slot(pool, slot, n):
    """Rewind lane ``slot`` by ``n`` speculative tokens.

    The inverse of ``n`` cache appends, for the speculative-decoding
    verify/reject cycle (DESIGN.md §Speculative-decoding): the lane's
    ``lengths`` drops by ``n`` and the rejected token rows
    ``[lengths - n, lengths)`` of every positional self-attention leaf —
    K/V, their absmax scales AND the packed LOP feature rows — are
    zeroed, restoring the lane bit-for-bit to its pool-init pattern at
    those positions (stale-masking alone would make the rows logically
    invisible, but bitwise lane equality is what the rollback property
    test pins). The per-lane PRNG ``sample_step`` rewinds by ``n`` too,
    so a sampled lane's key schedule stays aligned with its emission
    count — rolling back ``n`` of γ speculative tokens leaves the lane
    identical to having decoded γ−n tokens. Cross-attention pages and
    recurrent state are untouched (the encoder memory is never
    speculative; recurrent state cannot rewind, which is why engines
    without paged KV do not declare ``supports_speculative``).

    ``slot`` and ``n`` may be traced (one compile serves every lane and
    every rejection count); ``n`` clamps to the lane's length.
    """
    old_len = pool["lengths"][slot]
    new_len = jnp.maximum(old_len - n, 0)

    def walk(path, node):
        if isinstance(node, dict):
            return {k: walk(path + (k,), v) for k, v in node.items()}
        if path[-1] not in _POSITIONAL_KEYS or "cross" in path:
            return node
        sax = slot_axis(path, node)
        qax = seq_axis(path, node)
        start = (0,) * sax + (slot,) + (0,) * (node.ndim - sax - 1)
        sizes = node.shape[:sax] + (1,) + node.shape[sax + 1:]
        lane = jax.lax.dynamic_slice(node, start, sizes)
        pos = jnp.arange(lane.shape[qax])
        dead = (pos >= new_len) & (pos < old_len)
        shape = [1] * lane.ndim
        shape[qax] = lane.shape[qax]
        lane = jnp.where(dead.reshape(shape), jnp.zeros((), node.dtype),
                         lane)
        return jax.lax.dynamic_update_slice(node, lane, start)

    pool = walk((), dict(pool))
    pool["lengths"] = pool["lengths"].at[slot].set(new_len)
    if "sample_step" in pool:
        pool["sample_step"] = pool["sample_step"].at[slot].set(
            jnp.maximum(pool["sample_step"][slot] - n, 0))
    return pool


# ``free_slot`` is eviction under its queue-side name: a lane freed for the
# next admission. Kept as an alias so scheduler code reads naturally.
free_slot = evict_slot


def free_slots(pool) -> list[int]:
    """Host-side list of lanes currently free for admission (syncs)."""
    return [int(i) for i in
            np.flatnonzero(~np.asarray(pool["active"]))]


# ---------------------------------------------------------------------------
# Prefix store (hash-chain interning of computed prefill pages)
# ---------------------------------------------------------------------------

# per-lane vectors are not positional pages — the prefix carries lengths
# explicitly and never touches a lane's sampling state
_PER_LANE_KEYS = ("lengths", "cross_len", "active", "seed", "sample_step")


def _chain_key(parent_key: bytes, block_tokens: np.ndarray) -> bytes:
    """Hash-chain key of one token block given its parent's key."""
    h = hashlib.blake2b(parent_key, digest_size=16)
    h.update(np.ascontiguousarray(block_tokens, np.int32).tobytes())
    return h.digest()


def _page_checksum(pages) -> bytes:
    """Content checksum of one node's pages (DESIGN.md §Fault-tolerance).

    blake2b over every leaf's dtype, shape and raw bytes in sorted-path
    order — taken at intern time, re-verified on match, so a page that
    rots AFTER interning (bit flips, bad DMA) is caught before
    ``bulk_insert`` would fan the corruption into every hit lane.
    """
    h = hashlib.blake2b(digest_size=16)

    def walk(path, node):
        if isinstance(node, dict):
            for k in sorted(node):
                walk(path + (k,), node[k])
            return
        arr = np.asarray(node)
        h.update(repr((path, str(arr.dtype), arr.shape)).encode())
        h.update(np.ascontiguousarray(arr).tobytes())

    walk((), pages)
    return h.digest()


def _flip_one_bit(pages, rng):
    """Flip one deterministic bit somewhere in a page tree (the injected
    post-intern corruption of :mod:`repro.serving.faults`)."""
    leaves, treedef = jax.tree.flatten(pages)
    i = int(rng.integers(0, len(leaves)))
    arr = np.asarray(leaves[i]).copy()
    flat = arr.view(np.uint8).reshape(-1)
    byte = int(rng.integers(0, flat.size))
    flat[byte] ^= np.uint8(1 << int(rng.integers(0, 8)))
    leaves[i] = jnp.asarray(arr)
    return jax.tree.unflatten(treedef, leaves)


def _slice_pages(cache, lo: int, hi: int):
    """Token range [lo, hi) of every positional leaf of a batch-1 cache."""
    def walk(path, node):
        if isinstance(node, dict):
            return {k: walk(path + (k,), v) for k, v in node.items()
                    if k not in _PER_LANE_KEYS}
        ax = seq_axis(path, node)
        return node[(slice(None),) * ax + (slice(lo, hi),)]

    return walk((), cache)


def _concat_pages(trees):
    """Concatenate per-block page trees along each leaf's token axis."""
    def walk(path, nodes):
        if isinstance(nodes[0], dict):
            return {k: walk(path + (k,), [n[k] for n in nodes])
                    for k in nodes[0]}
        if len(nodes) == 1:
            return nodes[0]
        return jnp.concatenate(nodes, axis=seq_axis(path, nodes[0]))

    return walk((), trees)


class _PrefixNode:
    """One interned token block: its pages + its place in the radix chain.

    ``refs`` is the node's child count — a parent's pages are live as
    long as any longer chain extends through it, so only childless
    (``refs == 0``) nodes are eviction candidates.
    """

    __slots__ = ("key", "parent", "tokens", "n_tokens", "pages",
                 "children", "last_use", "checksum")

    def __init__(self, key, parent, tokens, n_tokens, pages,
                 checksum=None):
        self.key = key
        self.parent = parent
        self.tokens = tokens
        self.n_tokens = n_tokens           # cumulative tokens through here
        self.pages = pages
        self.children: dict = {}
        self.last_use = 0
        self.checksum = checksum           # blake2b of pages at intern

    @property
    def refs(self) -> int:
        return len(self.children)


class PrefixStore:
    """Hash/radix-keyed intern table over block-aligned cache pages.

    Host-side control structure (the pages themselves stay on device):
    block ``k`` of a prompt is keyed by
    ``blake2b(parent_key ‖ int32 tokens of block k)`` — a chain, so two
    prompts share node ``k`` iff their first ``(k+1)·block`` tokens are
    equal. Stored tokens are compared on every walk, so a hash collision
    degrades to a miss rather than resuming from someone else's prefill.

    ``match`` finds the longest *strict*-prefix chain of a prompt (at
    least one suffix token must remain to produce first-token logits);
    ``insert`` interns a computed batch-1 prefill's pages block by block
    (existing nodes are shared, not rewritten — the chunk-carry contract
    makes recomputed pages bitwise equal to the interned ones);
    ``assemble`` concatenates a chain's pages back into a batch-1 cache
    for :func:`bulk_insert`.

    Eviction is ref-counted LRU against ``max_tokens``: only childless
    nodes (``refs == 0``) retire, oldest ``last_use`` first, so a chain
    ages out leaf-to-root and a hot prefix's ancestry is never torn out
    from under it. Matching bumps the whole ancestry's recency. Clones
    happen synchronously at admit time, so an in-flight request never
    holds a store reference across serve cycles.
    """

    def __init__(self, block: int, *, max_tokens: int | None = None):
        assert block > 0
        self.block = int(block)
        self.max_tokens = max_tokens
        self._root = _PrefixNode(b"", None, None, 0, None)
        self._tick = 0
        self.cached_tokens = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.checksum_failures = 0

    def _walk_chain(self, tokens: np.ndarray, n_blocks: int):
        """Deepest existing node along ``tokens``'s first ``n_blocks``."""
        node = self._root
        for k in range(n_blocks):
            blk = tokens[k * self.block:(k + 1) * self.block]
            child = node.children.get(_chain_key(node.key, blk))
            if child is None or not np.array_equal(child.tokens, blk):
                break
            node = child
        return node

    def match(self, tokens) -> tuple[int, _PrefixNode | None]:
        """Longest interned strict prefix of ``tokens``.

        → ``(n_tokens, node)`` — the number of cached prompt tokens (a
        multiple of ``block``, always < ``len(tokens)``) and the chain
        node to clone from, or ``(0, None)`` on a miss. Bumps the
        matched ancestry's LRU recency.

        Every matched node's pages are re-verified against the checksum
        taken at intern time: a corrupted node (and the subtree hanging
        off it — its descendants resume from the corrupt pages) is
        dropped and the match truncates to the last clean ancestor, so
        corruption degrades to a shorter hit or a cold prefill instead
        of being cloned into every sharer. Raises
        :class:`repro.serving.faults.PrefixLookupError` when an active
        fault plan injects a store outage on this call — the scheduler
        treats it as a miss.
        """
        tokens = np.asarray(tokens, np.int32)
        if _faults.lookup_fails():
            raise _faults.PrefixLookupError(
                "injected prefix-store lookup failure")
        self._tick += 1
        node = self._walk_chain(tokens, max(0, (len(tokens) - 1)
                                           // self.block))
        node = self._verify_chain(node)
        if node is self._root:
            self.misses += 1
            return 0, None
        n = node
        while n is not self._root:
            n.last_use = self._tick
            n = n.parent
        self.hits += 1
        return node.n_tokens, node

    def _verify_chain(self, node: _PrefixNode) -> _PrefixNode:
        """Checksum the ancestry root→``node``; on the first mismatch
        drop that node's subtree and truncate the match to its parent."""
        chain = []
        n = node
        while n is not self._root:
            chain.append(n)
            n = n.parent
        for n in reversed(chain):
            if _page_checksum(n.pages) != n.checksum:
                self.checksum_failures += 1
                self._drop_subtree(n)
                return n.parent
        return node

    def _drop_subtree(self, node: _PrefixNode) -> None:
        """Remove ``node`` and every descendant from the store."""
        dropped = 1 + sum(1 for _ in self._iter_nodes(node))
        del node.parent.children[node.key]
        node.pages = None
        self.cached_tokens -= dropped * self.block

    def missing(self, tokens) -> bool:
        """True if interning ``tokens`` would create at least one node —
        the cheap pre-check that saves the lane extraction on re-inserts
        of an already-cached prefix."""
        tokens = np.asarray(tokens, np.int32)
        nb = len(tokens) // self.block
        return self._walk_chain(tokens, nb).n_tokens < nb * self.block

    def insert(self, tokens, cache) -> _PrefixNode | None:
        """Intern the block-aligned prefix of a computed prefill.

        ``tokens`` (length a multiple of ``block``; pass
        ``prompt[:plen // block * block]``) must be the first tokens the
        batch-1 ``cache`` was prefilled with. Existing chain nodes are
        reused; new blocks slice their pages out of ``cache``. Returns
        the chain's deepest node (None when ``tokens`` spans no block).
        """
        tokens = np.asarray(tokens, np.int32)
        nb = len(tokens) // self.block
        assert nb * self.block == len(tokens), \
            "insert() takes a block-aligned prefix"
        node = self._root
        self._tick += 1
        for k in range(nb):
            lo, hi = k * self.block, (k + 1) * self.block
            blk = tokens[lo:hi]
            key = _chain_key(node.key, blk)
            child = node.children.get(key)
            if child is not None:
                if not np.array_equal(child.tokens, blk):
                    break                  # hash collision: stop interning
                child.last_use = self._tick
                node = child
                continue
            pages = _slice_pages(cache, lo, hi)
            child = _PrefixNode(key, node, blk, hi, pages,
                                checksum=_page_checksum(pages))
            # injected post-intern rot: the checksum above was taken on
            # the clean pages, so the next match's verify catches this
            rng = _faults.page_corruption_rng()
            if rng is not None:
                child.pages = _flip_one_bit(child.pages, rng)
            child.last_use = self._tick
            node.children[key] = child
            self.cached_tokens += self.block
            node = child
        self._evict_cold()
        return None if node is self._root else node

    def assemble(self, node: _PrefixNode):
        """Chain pages root→``node`` as a batch-1 cache for
        :func:`bulk_insert` (token capacity = ``node.n_tokens``)."""
        chain = []
        n = node
        while n is not self._root:
            chain.append(n.pages)
            n = n.parent
        chain.reverse()
        cache = _concat_pages(chain)
        cache["lengths"] = jnp.full((1,), node.n_tokens, jnp.int32)
        return cache

    def _iter_nodes(self, node=None):
        node = node if node is not None else self._root
        for child in node.children.values():
            yield child
            yield from self._iter_nodes(child)

    def _evict_cold(self) -> None:
        """Retire cold childless nodes until under the token budget."""
        if self.max_tokens is None:
            return
        while self.cached_tokens > self.max_tokens:
            leaves = [n for n in self._iter_nodes() if not n.refs]
            if not leaves:
                break
            victim = min(leaves, key=lambda n: n.last_use)
            del victim.parent.children[victim.key]
            victim.pages = None
            self.cached_tokens -= self.block
            self.evictions += 1

    def check_invariants(self) -> None:
        """Structural invariants, asserted after every serve step under
        ``REPRO_PARANOID=1`` (DESIGN.md §Fault-tolerance): parent/child
        linkage and cumulative token counts are consistent, live nodes
        hold pages, ``cached_tokens`` equals the node count × block, and
        the token budget is only exceeded when every node is pinned by a
        child reference (the ref-counted eviction contract)."""
        n_nodes = 0
        for node in self._iter_nodes():
            assert node.parent.children.get(node.key) is node, \
                "prefix node detached from its parent"
            assert node.n_tokens == node.parent.n_tokens + self.block, \
                "prefix chain token count is not cumulative"
            assert node.pages is not None, "live prefix node lost its pages"
            n_nodes += 1
        assert self.cached_tokens == n_nodes * self.block, (
            f"cached_tokens={self.cached_tokens} but store holds "
            f"{n_nodes} blocks of {self.block}")
        if self.max_tokens is not None \
                and self.cached_tokens > self.max_tokens:
            assert all(n.refs for n in self._iter_nodes()), \
                "store over budget with evictable (childless) nodes"
