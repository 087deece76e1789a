"""Paged (block-pool) KV cache: allocator, cache construction, prefill insert.

Instead of every sequence owning a contiguous ``(max_len, Hkv, Dh)`` KV
buffer for its whole life, full-attention layers share a pool of
``n_blocks`` fixed-size blocks — ``(n_blocks, block_size, Hkv, Dh)`` per
layer — and each sequence owns a *list* of physical block ids, materialized
as a block table row ``(max_blocks,)``.  The block table is shared across
layers (the same logical allocation indexes every layer's pool), so
allocation is one host-side free-list operation per ``block_size`` generated
tokens, and a finished sequence's blocks are immediately reusable by queued
requests (continuous batching).

Physical block 0 is reserved as a scratch block: inactive server slots and
unallocated table entries point at it, so the fixed-shape decode step can
run over every slot unconditionally — writes land in scratch, reads are
masked by ``cache_len``.

Sliding-window attention layers keep their O(window) per-slot ring buffers
and recurrent mixers (RG-LRU / SSD) their O(1) states — paging only pays
where the cache grows with sequence length.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, LRU, ModelConfig

RESERVED_BLOCKS = 1  # physical block 0 = scratch for inactive slots


def needed_blocks(n_tokens: int, block_size: int) -> int:
    return -(-n_tokens // block_size)


class BlockAllocator:
    """Host-side free-list allocator over the physical block pool.

    Invariants (enforced): a block is owned by at most one sequence; free
    of an unowned block raises; block 0 is never handed out.  Tracks the
    in-use high-water mark for peak-memory accounting."""

    def __init__(self, n_blocks: int, block_size: int):
        if n_blocks <= RESERVED_BLOCKS:
            raise ValueError(f"pool needs > {RESERVED_BLOCKS} blocks, "
                             f"got {n_blocks}")
        self.n_blocks = n_blocks
        self.block_size = block_size
        self._free = list(range(n_blocks - 1, RESERVED_BLOCKS - 1, -1))
        self._used: set[int] = set()
        self.peak = 0

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return len(self._used)

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise MemoryError(f"asked for {n} blocks, {len(self._free)} free")
        ids = [self._free.pop() for _ in range(n)]
        self._used.update(ids)
        self.peak = max(self.peak, len(self._used))
        return ids

    def free(self, ids) -> None:
        for i in ids:
            if i not in self._used:
                raise ValueError(f"double/foreign free of block {i}")
            self._used.remove(i)
            self._free.append(i)

    def truncate_to(self, blocks: list[int], n_tokens: int) -> list[int]:
        """Free the tail of a sequence's block list in one call, keeping just
        enough blocks to cover ``n_tokens`` tokens.  Returns the retained
        prefix (a new list; the input is not mutated).

        The speculative-decode rejection path calls this after every verify
        step that rejects draft tokens; preemption recompute shares it with
        ``n_tokens=0`` (free everything)."""
        keep = needed_blocks(n_tokens, self.block_size) if n_tokens > 0 else 0
        if keep > len(blocks):
            raise ValueError(
                f"truncate_to({n_tokens}) needs {keep} blocks, "
                f"sequence owns {len(blocks)}")
        self.free(blocks[keep:])
        return list(blocks[:keep])

    def reset_peak(self) -> None:
        self.peak = len(self._used)


# ------------------------------------------------------------- construction

def _full_attn_specs(cfg: ModelConfig):
    return [s for s in cfg.layers if s.kind == ATTN and s.window is None]


def paged_cache_init(cfg: ModelConfig, n_slots: int, n_blocks: int,
                     block_size: int, max_len: int, dtype):
    """Build the decode-time cache tree for paged serving.

    Full-attention layers get shared pools ``(n_blocks, block_size, Hkv,
    Dh)`` (stacked over each scan group's repeat axis); window layers get
    per-slot ring buffers; recurrent mixers get per-slot states.  Returns
    the same list-of-groups structure as ``transformer.cache_init``."""
    from repro.models import rglru as R
    from repro.models import ssm as S
    from repro.models import transformer as T

    if cfg.family == "encdec":
        raise ValueError("paged serving does not support encdec configs")
    dt = jnp.dtype(dtype)
    caches = []
    for specs, n in T.groups_of(cfg):
        def one(spec):
            if spec.kind == ATTN:
                if spec.window is None:
                    shape = (n_blocks, block_size, cfg.n_kv_heads,
                             cfg.head_dim)
                else:
                    cap = min(spec.window, max_len)
                    shape = (n_slots, cap, cfg.n_kv_heads, cfg.head_dim)
                return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
            if spec.kind == LRU:
                return R.lru_state_init(cfg, n_slots, dt)
            return S.ssm_state_init(cfg, n_slots, dt)
        block = {f"b{i}": one(s) for i, s in enumerate(specs)}
        caches.append(jax.tree.map(
            lambda a: jnp.zeros((n,) + a.shape, a.dtype), block))
    return caches


def paged_insert(cfg: ModelConfig, caches, dense_caches, slots, table_rows,
                 prompt_len: int):
    """Scatter a batch of dense prefill caches into the paged caches.

    ``dense_caches`` comes from ``model.prefill(..., max_len=prompt_len)``
    on a (W, prompt_len) batch (leaves carry a leading scan axis then the
    batch axis; attention K/V keep their heads on one axis, Hkv*Dh);
    ``slots``: (W,) server slot indices — out-of-range entries (padding
    rows of a partially-filled admission batch) are dropped by the
    scatter; ``table_rows``: (W, nb) physical block ids covering each
    prompt, nb = ceil(prompt_len / block_size) (static) — padding rows
    point at the scratch block 0.  Jit-compatible: one program per
    (prompt_len bucket, W)."""
    from repro.models import transformer as T

    w = slots.shape[0]
    out = []
    for (specs, n), pc, dc in zip(T.groups_of(cfg), caches, dense_caches):
        grp = {}
        for i, spec in enumerate(specs):
            c, d = pc[f"b{i}"], dc[f"b{i}"]
            if spec.kind == ATTN and spec.window is None:
                bs = c["k"].shape[2]
                nb = needed_blocks(prompt_len, bs)
                assert table_rows.shape == (w, nb), (table_rows.shape, w, nb)
                pad = (-prompt_len) % bs
                def put(pool, dk):
                    x = dk[:, :, :prompt_len].reshape(
                        n, w, prompt_len, *pool.shape[3:])  # (n, W, P, H, Dh)
                    if pad:
                        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0),
                                        (0, 0)))
                    chunks = x.reshape(n, w * nb, bs, *x.shape[3:])
                    return pool.at[:, table_rows.reshape(-1)].set(
                        chunks.astype(pool.dtype))
                grp[f"b{i}"] = {"k": put(c["k"], d["k"]),
                                "v": put(c["v"], d["v"])}
            elif spec.kind == ATTN:
                cap_d = d["k"].shape[2]  # min(window, prompt_len)
                grp[f"b{i}"] = {
                    name: c[name].at[:, slots, :cap_d].set(
                        d[name].reshape(*d[name].shape[:3], *c[name].shape[3:])
                        .astype(c[name].dtype))
                    for name in ("k", "v")}
            else:  # recurrent state: copy rows
                grp[f"b{i}"] = jax.tree.map(
                    lambda cc, dd: cc.at[:, slots].set(
                        dd.astype(cc.dtype)), c, d)
        out.append(grp)
    return out


# --------------------------------------------------------------- accounting

def kv_pool_bytes(cfg: ModelConfig, n_blocks: int, block_size: int,
                  dtype) -> int:
    """Bytes of full-attention KV held in ``n_blocks`` pool blocks across
    all layers (k + v)."""
    per_tok = 2 * cfg.n_kv_heads * cfg.head_dim * jnp.dtype(dtype).itemsize
    return len(_full_attn_specs(cfg)) * n_blocks * block_size * per_tok


def full_buffer_bytes(cfg: ModelConfig, batch: int, max_len: int,
                      dtype) -> int:
    """Bytes of full-attention KV for ``batch`` contiguous ``max_len``
    buffers (the run-to-completion baseline's allocation)."""
    per_tok = 2 * cfg.n_kv_heads * cfg.head_dim * jnp.dtype(dtype).itemsize
    return len(_full_attn_specs(cfg)) * batch * max_len * per_tok
