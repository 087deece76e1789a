"""Serving launchers: the generation-side runtime that backs the
actor-generation function call, exposed standalone.

Two engines share the functional model API:

``BatchServer`` (legacy baseline) groups requests into prompt-length
buckets (rounded up to a power of two) so each bucket reuses one compiled
prefill+decode program.  Every request holds a full ``max_len`` KV buffer
for its whole life and a batch runs at the pace of its longest generation.

``ContinuousBatchServer`` is the production-shaped engine: one jitted
decode step over a fixed number of slots, a paged/block KV cache
(``models/paged_cache``) so a sequence only ever holds ``ceil(len /
block_size)`` blocks, and request admission *between* steps — a finished
sequence's slot and blocks are immediately reused by queued requests, so
short requests return as they complete instead of riding out the batch's
longest generation.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
        --requests 12 --new 16 --mode continuous
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import time


def bucket_of(length: int, buckets=(16, 32, 64, 128, 256, 512, 1024)) -> int:
    from repro.models.model import bucket_len
    return bucket_len(length, buckets)


class BatchServer:
    """Minimal bucketed batch server over the functional model API."""

    def __init__(self, cfg, params, max_new: int, pad_id: int = 0,
                 eos_id=None, temperature: float = 1.0, sampler: str = "cdf",
                 top_k: int = 0, top_p: float = 1.0, impl: str = "reference"):
        import jax
        from repro.models import generate
        self.cfg, self.params, self.max_new = cfg, params, max_new
        self.pad_id = pad_id
        self._gen = jax.jit(
            lambda p, b, k: generate(p, cfg, b, num_new_tokens=max_new,
                                     rng=k, temperature=temperature,
                                     eos_id=eos_id, sampler=sampler,
                                     top_k=top_k, top_p=top_p, impl=impl),
            static_argnames=())
        self._compiled_buckets = set()

    def serve(self, prompts, rng):
        """prompts: list of 1-D int32 arrays (ragged).  Returns a list of
        generated-token arrays, preserving order."""
        import jax.numpy as jnp
        by_bucket: dict[int, list[int]] = {}
        for i, pr in enumerate(prompts):
            by_bucket.setdefault(bucket_of(len(pr)), []).append(i)
        results = [None] * len(prompts)
        for bucket, idxs in sorted(by_bucket.items()):
            toks = jnp.full((len(idxs), bucket), self.pad_id, jnp.int32)
            for row, i in enumerate(idxs):
                pr = prompts[i]
                toks = toks.at[row, bucket - len(pr):].set(pr)  # left-pad
            out = self._gen(self.params, {"tokens": toks}, rng)
            self._compiled_buckets.add((len(idxs), bucket))
            for row, i in enumerate(idxs):
                results[i] = out["tokens"][row]
        return results


# --------------------------------------------------------------- continuous

@dataclasses.dataclass
class _Request:
    rid: int
    prompt: object  # np.ndarray int32
    max_new: int
    tokens: list = dataclasses.field(default_factory=list)
    logps: list = dataclasses.field(default_factory=list)
    blocks: list = dataclasses.field(default_factory=list)

    def reset(self):  # recompute-style preemption: restart from the prompt
        self.tokens, self.logps, self.blocks = [], [], []


class ContinuousBatchServer:
    """Continuous-batching decode engine over a paged KV cache.

    One jitted decode step runs every slot each iteration (fixed shapes —
    the same no-per-token-dispatch property as the scanned ``generate``
    loop, but across *requests*): per-row positions, a block table into the
    shared KV pool, fused sampling.  Between steps the host admits queued
    requests into freed slots (a batch=1 bucketed prefill fills freshly
    allocated blocks) and retires finished rows, freeing their blocks for
    reuse.  If the pool runs dry mid-flight the youngest active request is
    preempted (blocks freed, request requeued and recomputed later) so the
    oldest requests always make progress.

    Inactive slots point at the reserved scratch block 0 and are masked on
    the host — they ride along in the fixed-shape step at zero allocation
    cost.

    ``sync_every`` amortizes host<->device round trips: each dispatch runs
    that many decode steps as one compiled ``lax.scan`` chunk and the host
    only inspects tokens (EOS / length / admission) at chunk boundaries.
    Rows that finish mid-chunk decode a few throwaway tokens into their own
    (about-to-be-freed) blocks — bounded waste, large dispatch saving.

    Passing ``draft_params``/``draft_cfg`` opts into *speculative*
    continuous batching (models/spec.py): each dispatch cycle drafts
    ``spec_k`` tokens per active slot with the small model and verifies
    them in one prefill-shaped target dispatch; rejection sampling keeps
    every returned token (and logprob) exactly the target's.  Accepted
    prefixes keep their KV blocks, rejections truncate the row's block
    list (``BlockAllocator.truncate_to``).  The draft owns a statically
    laid-out block pool per slot — preemption/recompute only ever touches
    target blocks.  EOS and per-request ``max_new`` still apply: committed
    tokens are scanned in order and any overshoot suffix is discarded
    (dropping a suffix of exact samples does not bias the distribution).
    """

    def __init__(self, cfg, params, *, n_slots: int = 8,
                 kv_block_size: int = 16, max_kv_blocks: int = 0,
                 max_prompt: int = 128, max_new: int = 128,
                 eos_id=None, temperature: float = 1.0, sampler: str = "cdf",
                 top_k: int = 0, top_p: float = 1.0, impl: str = "reference",
                 pad_id: int = 0, sync_every: int = 4,
                 prompt_buckets=(16, 32, 64, 128, 256, 512, 1024),
                 draft_params=None, draft_cfg=None, spec_k: int = 4,
                 spec_controller=None):
        import jax
        import numpy as np
        from repro.models import paged_cache as PC

        if cfg.prefix_len and cfg.family != "encdec":
            raise ValueError("ContinuousBatchServer does not support prefix "
                             "(vlm) configs")
        if (draft_params is None) != (draft_cfg is None):
            raise ValueError("draft_params and draft_cfg go together")
        self.cfg, self.params = cfg, params
        self.n_slots, self.bs = n_slots, kv_block_size
        self.max_new, self.pad_id = max_new, pad_id
        self.eos_id, self.temperature = eos_id, temperature
        self.sampler, self.top_k, self.top_p = sampler, top_k, top_p
        self.impl = impl
        self.sync_every = max(1, sync_every)
        self.prompt_buckets = prompt_buckets
        self.max_len = bucket_of(max_prompt, prompt_buckets) + max_new
        self.draft_params, self.draft_cfg = draft_params, draft_cfg
        self.spec_k = spec_k
        self.spec_controller = spec_controller
        k_cap = 0
        if draft_cfg is not None:
            from repro.models.spec import check_spec_pair
            check_spec_pair(cfg, draft_cfg)
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            k_cap = (spec_controller.k_max if spec_controller is not None
                     else spec_k)
        self._k_cap = k_cap
        # chunked decode can overshoot a row's logical end by sync_every-1
        # positions before the host trims it (a verify cycle by spec_k) —
        # budget table + pool for it
        self.max_blocks = PC.needed_blocks(
            self.max_len + max(self.sync_every - 1, k_cap + 1), self.bs)
        if max_kv_blocks <= 0:  # worst case: every slot at full length
            max_kv_blocks = PC.RESERVED_BLOCKS + n_slots * self.max_blocks
        self.alloc = PC.BlockAllocator(max_kv_blocks, self.bs)
        self.caches = PC.paged_cache_init(
            cfg, n_slots, max_kv_blocks, self.bs, self.max_len, cfg.dtype)
        self.table = np.zeros((n_slots, self.max_blocks), np.int32)
        if draft_cfg is not None:
            from repro.models.spec import _draft_table
            self.d_table = _draft_table(n_slots, self.max_blocks)
            self.d_caches = PC.paged_cache_init(
                draft_cfg, n_slots, n_slots * self.max_blocks + 1, self.bs,
                self.max_len, draft_cfg.dtype)
            self._d_table_dev = None  # lazily jnp.asarray'd (static)
        self.seq_lens = np.zeros(n_slots, np.int32)
        self.cur_tok = np.zeros(n_slots, np.int32)
        self.slots: list = [None] * n_slots
        self.queue: collections.deque = collections.deque()
        self._rng = jax.random.PRNGKey(0)
        self._step_fns: dict = {}
        self._admit_fns: dict = {}
        self.steps = 0
        self.preemptions = 0
        self.compiles = 0
        self.completion_order: list[int] = []
        self._results: dict = {}
        self._latencies: dict = {}  # rid -> seconds from serve() entry
        self._t_serve0 = None
        self.spec_cycles = 0
        self.spec_accepted = 0
        self.spec_proposed = 0
        self.spec_k_trace: list[int] = []

    # -------------------------------------------------------- compiled fns
    def _donate(self):
        import jax
        # buffer donation is a no-op warning on CPU; keep logs clean there
        return jax.default_backend() != "cpu"

    def _step_fn(self, sampled: bool):
        """One dispatch = ``sync_every`` decode steps as a compiled scan."""
        import jax
        from repro.models import model as MDL
        fn = self._step_fns.get(sampled)
        if fn is None:
            self.compiles += 1
            k_steps = self.sync_every

            def run(p, caches, tbl, pos, tok, key):
                keys = jax.random.split(key, k_steps)

                def body(carry, kk):
                    tok, pos, caches = carry
                    ntok, lp, caches = MDL.paged_decode_and_sample_step(
                        p, self.cfg, tok, caches, tbl, pos,
                        kk if sampled else None,
                        temperature=self.temperature, sampler=self.sampler,
                        top_k=self.top_k, top_p=self.top_p, impl=self.impl)
                    return (ntok, pos + 1, caches), (ntok, lp)

                (_, _, caches), (toks, lps) = jax.lax.scan(
                    body, (tok, pos, caches), keys)
                return toks, lps, caches  # (k_steps, n_slots) each

            fn = self._step_fns[sampled] = jax.jit(
                run, donate_argnums=(1,) if self._donate() else ())
        return fn

    def _admit_fn(self, plen: int, width: int, sampled: bool,
                  draft: bool = False):
        """Fused batched prefill + first-token sample + paged-cache insert:
        one dispatch admits up to ``width`` same-bucket requests (padding
        rows carry slot index ``n_slots`` — dropped by the scatter — and
        scratch-block table rows).  One program per (prompt bucket, width,
        sampled?).  The ``draft`` variant prefills the draft model into its
        own pool (no sampling — the target's admission token is the one
        committed)."""
        import jax
        from repro.kernels import ops
        from repro.models import model as MDL
        from repro.models import paged_cache as PC
        cfg = self.draft_cfg if draft else self.cfg
        key_ = (plen, width, sampled, draft)
        fn = self._admit_fns.get(key_)
        if fn is None:
            self.compiles += 1

            def run(p, caches, batch, slots, table_rows, key):
                last_h, dense, _ = MDL.prefill(p, cfg, batch, max_len=plen,
                                               impl=self.impl)
                logits0 = MDL.logits_of(p, cfg, last_h[:, None])[:, 0]
                tok0, lp0 = ops.sample_logits(
                    logits0, key if sampled else None,
                    temperature=self.temperature, sampler=self.sampler,
                    top_k=self.top_k, top_p=self.top_p, impl=self.impl)
                caches = PC.paged_insert(cfg, caches, dense, slots,
                                         table_rows, plen)
                return tok0, lp0, caches

            fn = self._admit_fns[key_] = jax.jit(
                run, donate_argnums=(1,) if self._donate() else ())
        return fn

    def _next_key(self):
        import jax
        self._rng, k = jax.random.split(self._rng)
        return k

    # ----------------------------------------------------------- scheduling
    def _active(self):
        return [i for i, r in enumerate(self.slots) if r is not None]

    def _complete(self, slot: int):
        import numpy as np
        req = self.slots[slot]
        self._results[req.rid] = (np.asarray(req.tokens, np.int32),
                                  np.asarray(req.logps, np.float32))
        self.completion_order.append(req.rid)
        if self._t_serve0 is not None:
            self._latencies[req.rid] = time.perf_counter() - self._t_serve0
        req.blocks = self.alloc.truncate_to(req.blocks, 0)
        self.table[slot, :] = 0
        self.seq_lens[slot] = 0
        self.cur_tok[slot] = 0
        self.slots[slot] = None

    def _preempt(self, slot: int):
        """Recompute-style preemption: free the victim's blocks (a
        truncate-to-zero — the same path a rejected speculative draft takes,
        just all the way down) and requeue it (it restarts from its prompt
        on re-admission), re-inserted in arrival order so FCFS admission is
        preserved."""
        req = self.slots[slot]
        req.blocks = self.alloc.truncate_to(req.blocks, 0)
        req.reset()
        idx = 0
        while idx < len(self.queue) and self.queue[idx].rid < req.rid:
            idx += 1
        self.queue.insert(idx, req)
        self.table[slot, :] = 0
        self.seq_lens[slot] = 0
        self.cur_tok[slot] = 0
        self.slots[slot] = None
        self.preemptions += 1

    def _try_admit(self, sampled: bool):
        """Admit queued requests into free slots, batching every queued
        request that shares the head's prompt bucket into ONE fused
        prefill+insert dispatch (FCFS within a bucket; the head's bucket is
        always served first, so no starvation)."""
        import jax.numpy as jnp
        import numpy as np
        from repro.models import paged_cache as PC
        while self.queue:
            free = [i for i, r in enumerate(self.slots) if r is None]
            if not free:
                return
            head = self.queue[0]
            pb = bucket_of(len(head.prompt), self.prompt_buckets)
            nb = PC.needed_blocks(pb, self.bs)
            # same-bucket requests, as many as slots and blocks allow
            batch_reqs, budget = [], self.alloc.free_count
            for req in self.queue:
                if len(batch_reqs) >= len(free) or budget < nb:
                    break
                if bucket_of(len(req.prompt), self.prompt_buckets) != pb:
                    continue
                batch_reqs.append(req)
                budget -= nb
            if not batch_reqs:
                return  # head can't fit yet: wait for completions
            for req in batch_reqs:
                self.queue.remove(req)
            k = len(batch_reqs)
            width = 1
            while width < k:
                width *= 2
            toks = np.full((width, pb), self.pad_id, np.int32)
            slots_arr = np.full((width,), self.n_slots, np.int32)  # dropped
            table_arr = np.zeros((width, nb), np.int32)  # scratch block 0
            for row, req in enumerate(batch_reqs):
                req.blocks = self.alloc.alloc(nb)
                toks[row, pb - len(req.prompt):] = req.prompt  # left-pad
                slots_arr[row] = free[row]
                table_arr[row] = req.blocks
            tok0, lp0, self.caches = self._admit_fn(pb, width, sampled)(
                self.params, self.caches, {"tokens": jnp.asarray(toks)},
                jnp.asarray(slots_arr), jnp.asarray(table_arr),
                self._next_key())
            tok0, lp0 = np.asarray(tok0), np.asarray(lp0)
            if self.draft_cfg is not None:
                # mirror the prompt into the draft's statically-owned rows
                d_rows = np.zeros((width, nb), np.int32)
                for row in range(len(batch_reqs)):
                    d_rows[row] = self.d_table[free[row], :nb]
                _, _, self.d_caches = self._admit_fn(
                    pb, width, False, draft=True)(
                    self.draft_params, self.d_caches,
                    {"tokens": jnp.asarray(toks)}, jnp.asarray(slots_arr),
                    jnp.asarray(d_rows), self._next_key())
            for row, req in enumerate(batch_reqs):
                slot = free[row]
                req.tokens.append(int(tok0[row]))
                req.logps.append(float(lp0[row]))
                self.table[slot, :] = 0
                self.table[slot, :nb] = req.blocks
                self.seq_lens[slot] = pb
                self.cur_tok[slot] = req.tokens[-1]
                self.slots[slot] = req
                if (len(req.tokens) >= req.max_new
                        or (self.eos_id is not None
                            and req.tokens[-1] == self.eos_id)):
                    self._complete(slot)

    def _ensure_blocks(self, span=None):
        """Grow each active row's block list to cover the whole upcoming
        dispatch — ``span`` positions past the current one (default: the
        ``sync_every`` decode chunk; a speculative verify passes its draft
        length) — preempting the youngest request when the pool runs dry.

        Rows grow oldest-first, and a row never evicts an older one — if
        only older rows remain as victims, the growing row preempts
        *itself* — so the oldest request always makes forward progress."""
        if span is None:
            span = self.sync_every - 1
        for slot in sorted(self._active(),
                           key=lambda s: self.slots[s].rid):
            req = self.slots[slot]
            if req is None:  # preempted by an earlier iteration
                continue
            need = (int(self.seq_lens[slot]) + span) // self.bs
            while need >= len(req.blocks):
                if self.alloc.free_count > 0:
                    blk = self.alloc.alloc(1)[0]
                    self.table[slot, len(req.blocks)] = blk
                    req.blocks.append(blk)
                    continue
                victims = [s for s in self._active() if s != slot]
                if not victims:
                    raise MemoryError(
                        "KV pool too small for a single request; raise "
                        "max_kv_blocks")
                victim = max(victims, key=lambda s: self.slots[s].rid)
                if self.slots[victim].rid < req.rid:
                    self._preempt(slot)  # everyone else is older: yield
                    break
                self._preempt(victim)

    def _decode_step(self, sampled: bool):
        """One dispatch: ``sync_every`` decode steps for every slot, then
        host-side retirement.  A row finishing mid-chunk has its throwaway
        tail tokens dropped here (their KV went into blocks that are freed
        immediately below)."""
        import jax.numpy as jnp
        import numpy as np
        self._ensure_blocks()
        toks, lps, self.caches = self._step_fn(sampled)(
            self.params, self.caches, jnp.asarray(self.table),
            jnp.asarray(self.seq_lens), jnp.asarray(self.cur_tok),
            self._next_key())
        toks, lps = np.asarray(toks), np.asarray(lps)  # (k, n_slots)
        self.steps += 1
        for slot in self._active():
            req = self.slots[slot]
            for j in range(self.sync_every):
                self.seq_lens[slot] += 1
                t = int(toks[j, slot])
                req.tokens.append(t)
                req.logps.append(float(lps[j, slot]))
                self.cur_tok[slot] = t
                if (len(req.tokens) >= req.max_new
                        or (self.eos_id is not None and t == self.eos_id)):
                    self._complete(slot)
                    break

    def _spec_step(self, sampled: bool):
        """One speculative cycle for every slot: k+1 fused draft steps (the
        last is the consume-only catch-up), one prefill-shaped target verify
        over the k+1 spec positions, batched rejection sampling, host-side
        commit.  Inactive slots ride along against scratch block 0 exactly
        as in ``_decode_step``; their outputs are ignored.  Committed tokens
        and logprobs are exact target samples, so EOS / max_new trimming is
        a pure suffix drop."""
        import jax.numpy as jnp
        import numpy as np
        from repro.models import spec as SPEC
        ctl = self.spec_controller
        k = ctl.k if ctl is not None else self.spec_k
        self.spec_k_trace.append(k)
        # span=k+1: verify writes positions seq_lens..seq_lens+k, and a
        # clean sweep commits k+1 tokens so the post-commit truncate_to
        # keeps blocks covering index seq_lens+k+1
        self._ensure_blocks(span=k + 1)
        if self._d_table_dev is None:
            self._d_table_dev = jnp.asarray(self.d_table)
        pos0 = self.seq_lens.astype(np.int32)
        draft = SPEC._draft_run(self.draft_cfg, sampled, self.temperature,
                                self.sampler, self.top_k, self.top_p,
                                self.impl)
        verify = SPEC._verify_run(self.cfg, sampled, self.temperature,
                                  self.top_k, self.top_p, self.impl)
        keys = (jnp.stack([self._next_key() for _ in range(k + 1)])
                if sampled else jnp.zeros((k + 1, 2), jnp.uint32))
        dtoks, dlgs, self.d_caches = draft(
            self.draft_params, self.d_caches, self._d_table_dev,
            jnp.asarray(self.cur_tok), jnp.asarray(pos0), keys)
        dtoks = np.asarray(dtoks)[:, :k]
        dlgs_dev = jnp.asarray(np.asarray(dlgs)[:, :k])
        tokens = np.concatenate([self.cur_tok[:, None], dtoks], axis=1)
        positions = pos0[:, None] + np.arange(k + 1, dtype=np.int32)[None]
        acc, ytok, ylp, dlps, self.caches = verify(
            self.params, self.caches, jnp.asarray(self.table),
            jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(dtoks), dlgs_dev, self._next_key())
        acc, ytok = np.asarray(acc), np.asarray(ytok)
        ylp, dlps = np.asarray(ylp), np.asarray(dlps)
        self.steps += 1
        self.spec_cycles += 1
        cyc_acc = cyc_prop = 0
        for slot in self._active():
            req = self.slots[slot]
            r = int(acc[slot])
            cyc_acc += r
            cyc_prop += k
            committed = [(int(tokens[slot, 1 + j]), float(dlps[slot, j]))
                         for j in range(r)] + [(int(ytok[slot]),
                                                float(ylp[slot]))]
            for t, lp in committed:
                self.seq_lens[slot] += 1
                req.tokens.append(t)
                req.logps.append(float(lp))
                self.cur_tok[slot] = t
                if (len(req.tokens) >= req.max_new
                        or (self.eos_id is not None and t == self.eos_id)):
                    self._complete(slot)
                    break
            else:
                # row survives: drop the blocks past the committed length
                # (prompt bucket is already folded into seq_lens)
                c = int(self.seq_lens[slot]) + 1
                req.blocks = self.alloc.truncate_to(req.blocks, c)
                self.table[slot, len(req.blocks):] = 0
        self.spec_accepted += cyc_acc
        self.spec_proposed += cyc_prop
        if ctl is not None and cyc_prop:
            ctl.update(cyc_acc / cyc_prop)

    # -------------------------------------------------------------- serving
    def serve(self, prompts, rng=None, max_new=None):
        """prompts: list of 1-D int32 arrays (ragged).  ``max_new``: int or
        per-request list (default: the server's ``max_new``).  ``rng=None``
        decodes greedily.  Returns (tokens_list, logps_list) in request
        order; requests *complete* out of order (see
        ``completion_order``)."""
        import numpy as np
        if rng is not None:
            self._rng = rng
        sampled = rng is not None
        n = len(prompts)
        if max_new is None:
            max_new = self.max_new
        per_req = list(max_new) if hasattr(max_new, "__len__") \
            else [max_new] * n
        if len(per_req) != n:
            raise ValueError(f"max_new has {len(per_req)} entries for "
                             f"{n} prompts")
        base = len(self._results)
        reqs = [_Request(rid=base + i, prompt=np.asarray(p, np.int32),
                         max_new=int(m)) for i, (p, m)
                in enumerate(zip(prompts, per_req))]
        # validate before any work: a bad request must be rejected here,
        # not raise mid-flight out of _try_admit (which would lose every
        # in-flight request and leave the queue poisoned)
        for r in reqs:
            if r.max_new < 1:
                raise ValueError(f"request {r.rid}: max_new must be >= 1")
            pb = bucket_of(len(r.prompt), self.prompt_buckets)
            if pb + r.max_new > self.max_len:
                raise ValueError(
                    f"request {r.rid}: prompt bucket {pb} + max_new "
                    f"{r.max_new} exceeds max_len {self.max_len}")
        self.queue.extend(reqs)
        # per-request latency clock; restarted (and the samples reset) per
        # serve() call so stats() reflects the most recent cohort
        self._latencies = {}
        self._t_serve0 = time.perf_counter()
        spec = self.draft_cfg is not None
        while self.queue or self._active():
            self._try_admit(sampled)
            if self._active():
                if spec:
                    self._spec_step(sampled)
                else:
                    self._decode_step(sampled)
            elif self.queue:
                raise MemoryError(
                    "queued request cannot be admitted into an empty "
                    "server; raise max_kv_blocks")
        toks = [self._results[r.rid][0] for r in reqs]
        lps = [self._results[r.rid][1] for r in reqs]
        return toks, lps

    def stats(self) -> dict:
        out = {"steps": self.steps, "preemptions": self.preemptions,
               "compiles": self.compiles, "peak_blocks": self.alloc.peak,
               "completion_order": list(self.completion_order)}
        if self._latencies:
            lats = sorted(self._latencies.values())

            def pct(q):
                return lats[min(len(lats) - 1, int(q * len(lats)))]
            out["latency_s"] = {"p50": pct(0.50), "p99": pct(0.99),
                                "n": len(lats)}
        if self.draft_cfg is not None:
            out.update(
                spec_cycles=self.spec_cycles,
                spec_accepted=self.spec_accepted,
                spec_proposed=self.spec_proposed,
                spec_accept_rate=(self.spec_accepted
                                  / max(self.spec_proposed, 1)),
                spec_k_trace=list(self.spec_k_trace))
        return out

    def kv_peak_bytes(self) -> int:
        from repro.models import paged_cache as PC
        return PC.kv_pool_bytes(self.cfg, self.alloc.peak, self.bs,
                                self.cfg.dtype)


def build_server(cfg, params, exp, *, max_prompt: int = 128,
                 max_new: int = 128, draft_params=None):
    """Construct the serve engine selected by ``ExperimentConfig.serve_mode``
    ("bucketed" | "continuous"), plumbing the sampler/kv knobs through.
    With ``exp.draft_model`` set AND ``draft_params`` given, the continuous
    engine runs speculative draft-and-verify cycles."""
    if exp.serve_mode == "bucketed":
        return BatchServer(cfg, params, max_new=max_new, eos_id=exp.eos_id,
                           sampler=exp.sampler, top_k=exp.top_k,
                           top_p=exp.top_p,
                           impl=exp.rollout_impl or exp.impl)
    if exp.serve_mode != "continuous":
        raise ValueError(f"serve_mode={exp.serve_mode!r} not in "
                         "('bucketed', 'continuous')")
    spec_kw = {}
    if draft_params is not None and getattr(exp, "draft_model", None) \
            is not None:
        from repro.models.spec import SpecController
        spec_kw = dict(
            draft_params=draft_params, draft_cfg=exp.draft_model,
            spec_k=exp.spec_k,
            spec_controller=(SpecController(init_k=exp.spec_k)
                             if exp.spec_adaptive else None))
    return ContinuousBatchServer(
        cfg, params, kv_block_size=exp.kv_block_size,
        max_kv_blocks=exp.max_kv_blocks, max_prompt=max_prompt,
        max_new=max_new, eos_id=exp.eos_id, sampler=exp.sampler,
        top_k=exp.top_k, top_p=exp.top_p,
        impl=exp.rollout_impl or exp.impl, **spec_kw)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--new", type=int, default=16)
    ap.add_argument("--mode", default="continuous",
                    choices=["bucketed", "continuous"])
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--spec", action="store_true",
                    help="speculative decoding demo (self-draft: the target "
                         "drafts for itself, accept rate ~1)")
    ap.add_argument("--spec-k", type=int, default=4)
    args = ap.parse_args()

    import jax
    import numpy as np
    from repro.configs import ARCHS
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import init_params

    enable_compile_cache()
    cfg = ARCHS[args.arch]
    if args.smoke:
        cfg = cfg.reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)

    rng = np.random.default_rng(0)
    prompts = [np.asarray(rng.integers(1, cfg.vocab_size, rng.integers(4, 40)),
                          np.int32) for _ in range(args.requests)]
    t0 = time.time()
    if args.mode == "bucketed":
        server = BatchServer(cfg, params, max_new=args.new)
        out = server.serve(prompts, jax.random.PRNGKey(1))
        extra = f"buckets={sorted(server._compiled_buckets)}"
    else:
        spec_kw = {}
        if args.spec:
            spec_kw = dict(draft_params=params, draft_cfg=cfg,
                           spec_k=args.spec_k)
        server = ContinuousBatchServer(
            cfg, params, n_slots=args.slots, kv_block_size=args.block_size,
            max_prompt=64, max_new=args.new, **spec_kw)
        out, _ = server.serve(prompts, jax.random.PRNGKey(1))
        st = server.stats()
        extra = (f"steps={st['steps']} peak_blocks={st['peak_blocks']} "
                 f"kv_peak={server.kv_peak_bytes()}B")
        if args.spec:
            extra += (f" accept={st['spec_accept_rate']:.2f} "
                      f"cycles={st['spec_cycles']}")
    dt = time.time() - t0
    toks = sum(len(o) for o in out)
    print(f"served {len(prompts)} ragged requests in {dt:.1f}s "
          f"({toks} new tokens, mode={args.mode}, {extra})")
    print("first output:", np.asarray(out[0][:8]).tolist())


if __name__ == "__main__":
    main()
