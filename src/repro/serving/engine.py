"""Continuous-batching serving engine.

Wires the host-side scheduler (``serving/scheduler.py``) to the device-side
paged model step (``models/transformer.decode_step_paged`` over a
``PagedKVCache``). One ``step()`` is one unit of virtual time:

  1. admit arrived requests (FCFS within priority class) while a decode
     slot and enough cache blocks exist; each admission runs a jitted
     prefill (per length bucket) and scatters the prompt KV into its pages
     — resumed requests restore their saved pages instead (the preemption
     round-trip is bitwise);
  2. grow each running sequence's block list for the token this step
     writes, preempting victims on exhaustion (their pages are copied to
     host before the blocks free);
  3. one jitted decode over ALL slots — inactive rows point at the shared
     scratch page and their outputs are dropped, so the decode shape is
     static and every live row's numbers are independent of batch
     composition (the interleaving-equivalence property the test battery
     checks bitwise);
  4. record tokens, retire on EOS / max-new-tokens, free blocks.

The model half sits behind a tiny protocol (``prefill``/``decode``/
``save_blocks``/``restore_blocks``) so the scheduler battery runs against
a deterministic host-only stub (``StubModel``) with no compilation, while
``PagedModel`` is the real thing — optionally holding the cache fp8 via
``precision=`` and distributing decode attention with ``ring_decode`` over
a mesh's ``data`` axis. A model with latent attention (MLA) keeps a latent
page pool instead of per-head K/V (``serving/paged_cache.py``), on one
device and in the model's dtype.
"""
from __future__ import annotations

import math

import numpy as np

from repro.serving import scheduler as sched
from repro.serving.scheduler import NULL_BLOCK, Request
from repro.serving.tracing import span, step_span

PREFILL_BUCKET = 128  # prompts are padded to a multiple of this many tokens

__all__ = ["ServingEngine", "PagedModel", "StubModel", "Request"]


class StubModel:
    """Deterministic host-only model stub for scheduler tests.

    Token streams follow a per-sequence integer recurrence seeded by the
    last prompt token, so any slot/cache mix-up between sequences derails
    the stream — exactly what the battery's isolation properties detect.
    ``save/restore`` round-trip per-logical-block token counters so
    preemption bookkeeping is exercised too.
    """

    def __init__(self, vocab: int = 251):
        self.vocab = vocab
        self.block_writes: dict[int, list] = {}  # rid -> per-step log

    def _next(self, token: int, position: int) -> int:
        return (token * 31 + position * 7 + 13) % self.vocab

    def prefill(self, seq, block_ids):
        prompt = seq.req.prompt
        self.block_writes.setdefault(seq.rid, []).append(
            ("prefill", tuple(block_ids))
        )
        return self._next(prompt[-1], len(prompt) - 1)

    def decode(self, slot_tokens, slot_positions, slot_tables, active):
        out = np.zeros(len(slot_tokens), np.int64)
        for i in range(len(slot_tokens)):
            out[i] = self._next(int(slot_tokens[i]), int(slot_positions[i]))
        return out

    def save_blocks(self, seq, block_ids):
        return ("payload", seq.rid, len(block_ids))

    def restore_blocks(self, seq, block_ids, payload):
        tag, rid, n = payload
        assert tag == "payload" and rid == seq.rid and n <= len(block_ids)


class PagedModel:
    """The real model half: jitted paged prefill + decode over a
    ``PagedKVCache`` (dense/moe transformer families).

    On one device the cache pages are the scheduler's own block ids. With
    a ``mesh``, decode attention is the ring over ``mesh[ring_axis]``
    (``serving.ring_decode``), which needs every sequence's logical block
    j on shard ``j // NB_l`` (``NB_l = max_blocks_per_seq / n``). The
    scheduler's ids carry no placement, so the model lays the pool out by
    slot instead: each shard holds a scratch page plus ``NB_l`` pages per
    slot, and slot s keeps logical block j in page
    ``(j // NB_l) * shard_pages + 1 + s * NB_l + j % NB_l``. The weights are
    placed replicated and the pools sharded on their page axis once, here,
    and every step keeps them there.

    A prompt is prefilled padded to a multiple of ``PREFILL_BUCKET`` tokens
    (and of the page size), so that a mix of lengths compiles a few prefill
    programs; the padding's pages past the grant are the scratch page.
    Under a grouped MoE the decode program also returns how many (layer,
    held expert) pairs computed a row, read back with the sampled tokens
    (``serve.decode.experts``), and each prefill the rows its held experts
    computed (``serve.prefill.experts``).
    """

    def __init__(self, cfg, params, *, num_blocks, block_size, max_slots,
                 max_blocks_per_seq, precision=None, impl=None, mesh=None,
                 ring_axis: str = "data"):
        import jax
        import jax.numpy as jnp

        from repro.models import transformer
        from repro.serving import paged_cache, ring_decode

        if cfg.family not in ("dense", "moe"):
            raise NotImplementedError(
                f"PagedModel serves the transformer families (dense/moe), "
                f"got {cfg.family!r}"
            )
        if cfg.mla and (mesh is not None or precision is not None):
            raise NotImplementedError(
                f"{cfg.name}: the latent page pool is served on one device "
                f"in the model's dtype; got mesh={mesh}, "
                f"precision={precision}")
        self._jax, self._jnp = jax, jnp
        self._transformer = transformer
        self.cfg, self.params = cfg, params
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        self.vocab = cfg.vocab_size
        self.impl = impl
        self.mesh = mesh
        self.ring_axis = ring_axis
        self.tables = np.full(
            (max_slots, max_blocks_per_seq), NULL_BLOCK, np.int32
        )
        self._stats = cfg.grouped_moe
        attn_fn = None
        if impl is not None and cfg.mla:
            import functools

            from repro.kernels import ops

            attn_fn = functools.partial(ops.latent_decode_attention,
                                        impl=impl)
        elif impl is not None:
            from repro.kernels import ops

            def attn_fn(q, kp, vp, ks, vs, tbl, pos, window, layer):
                return ops.decode_attention(
                    q, kp, vp, pos, paged=True, block_table=tbl, k_scale=ks,
                    v_scale=vs, window=window, impl=impl, layer=layer,
                )
        pool_blocks = num_blocks
        self._pool_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            n = mesh.shape[ring_axis]
            if max_blocks_per_seq % n:
                raise ValueError(
                    "ring decode needs max_blocks_per_seq divisible by the "
                    f"{ring_axis} axis ({n})"
                )
            self._nb_l = max_blocks_per_seq // n
            self._shard_pages = max_slots * self._nb_l + 1
            pool_blocks = n * self._shard_pages
            self._pool_sharding = NamedSharding(mesh, P(None, ring_axis))
            self.params = jax.device_put(params, NamedSharding(mesh, P()))
            shard_pages = self._shard_pages

            def attn_fn(q, kp, vp, ks, vs, tbl, pos, window, layer):
                # column j's page lies on its owning shard: reduce the
                # global page id to that shard's local one
                return ring_decode.ring_decode(
                    q, kp, vp, tbl % shard_pages, pos, mesh, axis=ring_axis,
                    window=window, k_scale=ks, v_scale=vs, impl=impl,
                    layer=layer,
                )

        self.cache = self._place(paged_cache.init_paged_cache(
            cfg, num_blocks=pool_blocks, block_size=block_size,
            policy=None if precision is None else getattr(
                precision, "name", precision
            ),
        ))
        self._attn_fn = attn_fn

        def serve_decode(p, c, b):
            if self._stats:
                logits, c, stats = transformer.decode_step_paged(
                    p, cfg, c, b, attn_fn=attn_fn, stats=True)
                return logits, self._place(c), stats["experts_hit"]
            logits, c = transformer.decode_step_paged(
                p, cfg, c, b, attn_fn=attn_fn
            )
            return logits, self._place(c)

        self._decode_jit = jax.jit(serve_decode, donate_argnums=(1,))
        self._prefill_jit: dict[int, object] = {}  # per length bucket
        self.last_logits = None  # newest decode step's (max_slots, V_pad)

    def _place(self, cache):
        """Pin the pools to their page-axis sharding (no-op on one device):
        once at construction, and as a constraint inside every step."""
        if self._pool_sharding is None:
            return cache
        return self._jax.lax.with_sharding_constraint(cache,
                                                      self._pool_sharding)

    def _pages(self, slot, block_ids):
        """Device page ids holding a sequence's logical blocks, in order."""
        if self.mesh is None:
            return list(block_ids)
        nb_l = self._nb_l
        return [(j // nb_l) * self._shard_pages + 1 + slot * nb_l + j % nb_l
                for j in range(len(block_ids))]

    # -- prefill ------------------------------------------------------------

    def _bucket(self, s0: int) -> int:
        step = math.lcm(PREFILL_BUCKET, self.block_size)
        return step * math.ceil(s0 / step)

    def _prefill_fn(self, sb: int):
        jax, jnp = self._jax, self._jnp
        cfg, tr = self.cfg, self._transformer
        if sb not in self._prefill_jit:
            nbp = sb // self.block_size

            def prompt(params, tokens, length):
                if self._stats:
                    return tr.prefill_step(
                        params, cfg, {"tokens": tokens, "length": length},
                        max_len=sb, stats=True)
                return tr.prefill_step(params, cfg, {"tokens": tokens},
                                       max_len=sb)

            if self.mesh is not None:
                # every device prefills its own replica: a Pallas kernel is
                # never partitioned by the compiler, only inside shard_map
                from jax.sharding import PartitionSpec as P

                prompt = jax.shard_map(
                    prompt, mesh=self.mesh, in_specs=(P(), P(), P()),
                    out_specs=(P(),) * (3 if self._stats else 2),
                    check_vma=False)

            def latent_rows(x):  # (n, 1, sb, D) -> (n, nbp, 1, bs, D)
                return x.reshape(x.shape[0], nbp, 1, self.block_size,
                                 x.shape[-1])

            def serve_prefill(params, cache, tokens, block_ids, last_idx):
                # tokens (1, sb) padded prompt; causal attention keeps every
                # real row independent of the padded tail
                logits, kv, *stats = prompt(params, tokens, last_idx + 1)
                if cfg.mla:
                    cache = self._place(cache.write_prompt(
                        block_ids, latent_rows(kv["latent"]),
                        lead_rows=(latent_rows(kv["lead"]) if "lead" in kv
                                   else None)))
                else:
                    nl, _, K, _, hd = kv["k"].shape
                    def rows(x):  # (nl, nbp, K, bs, hd)
                        return jnp.moveaxis(
                            x[:, 0].reshape(nl, K, nbp, self.block_size, hd),
                            2, 1
                        )
                    cache = self._place(cache.write_prompt(
                        block_ids, rows(kv["k"]), rows(kv["v"])))
                first = jnp.argmax(
                    logits[0, last_idx, : cfg.vocab_size]
                ).astype(jnp.int32)
                if stats:
                    return cache, first, stats[0]["held_rows"]
                return cache, first

            self._prefill_jit[sb] = jax.jit(serve_prefill,
                                            donate_argnums=(1,))
        return self._prefill_jit[sb]

    def prefill(self, seq, block_ids):
        jnp = self._jnp
        prompt = seq.req.prompt
        sb = self._bucket(len(prompt))
        tokens = np.zeros((1, sb), np.int32)
        tokens[0, : len(prompt)] = prompt
        pages = self._pages(seq.slot, block_ids)
        ids = np.full(sb // self.block_size, NULL_BLOCK, np.int32)
        ids[: len(pages)] = pages  # prompt pages (grant covers them)
        with span("prefill", rid=seq.rid, tokens=len(prompt)):
            self.cache, first, *held = self._prefill_fn(sb)(
                self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(ids), jnp.int32(len(prompt) - 1),
            )
            if held:
                first, held = self._jax.device_get((first, held[0]))
                with span("prefill.experts", held_rows=int(held)):
                    pass
            first = int(first)
        self.tables[seq.slot, :] = NULL_BLOCK
        self.tables[seq.slot, : len(pages)] = pages
        return first

    # -- decode -------------------------------------------------------------

    def sync_table(self, seq) -> None:
        """Mirror the scheduler's block list into the device table row."""
        self.tables[seq.slot, :] = NULL_BLOCK
        self.tables[seq.slot, : len(seq.blocks)] = self._pages(seq.slot,
                                                               seq.blocks)

    def live_pages(self, slot_positions) -> int:
        """Table columns the decode attention reads this step, summed over
        the rows: each row's pages up to its position (from the first page
        of its lookback window, when the model has one)."""
        pos = np.asarray(slot_positions)
        last = pos // self.block_size
        first = 0
        if self.cfg.sliding_window:
            first = np.maximum((pos - self.cfg.sliding_window + 1)
                               // self.block_size, 0)
        return int(np.sum(last - first + 1))

    def decode(self, slot_tokens, slot_positions, slot_tables, active):
        jnp = self._jnp
        with span("decode", live=int(np.count_nonzero(active)),
                  slots=len(active), pages=self.live_pages(slot_positions),
                  width=self.max_blocks_per_seq):
            with span("decode.launch"):
                batch = {
                    "token": jnp.asarray(slot_tokens, jnp.int32),
                    "position": jnp.asarray(slot_positions, jnp.int32),
                    "block_table": jnp.asarray(slot_tables, jnp.int32),
                }
                logits, self.cache, *hit = self._decode_jit(
                    self.params, self.cache, batch)
            self.last_logits = logits
            if hit:
                with span("decode.sample"):
                    out, hit = self._jax.device_get(
                        (jnp.argmax(logits[:, : self.vocab], axis=-1),
                         hit[0]))
                with span("decode.experts", experts_hit=int(hit)):
                    return np.asarray(out).astype(np.int64)
            with span("decode.sample"):
                return np.asarray(
                    jnp.argmax(logits[:, : self.vocab], axis=-1)
                ).astype(np.int64)

    # -- preemption payloads -------------------------------------------------

    def save_blocks(self, seq, block_ids):
        jax = self._jax
        ids = np.asarray(self._pages(seq.slot, block_ids), np.int32)
        return jax.device_get(self.cache.gather_blocks(ids))

    def restore_blocks(self, seq, block_ids, payload):
        jnp = self._jnp
        n = payload["k"].shape[1]
        ids = jnp.asarray(np.asarray(self._pages(seq.slot, block_ids[:n]),
                                     np.int32))
        self.cache = self._place(self.cache.restore_blocks(ids, payload))


class ServingEngine:
    """Open-loop continuous-batching engine over a paged KV cache."""

    def __init__(self, model, *, num_blocks, block_size, max_slots,
                 max_blocks_per_seq, eos_id: int | None = None):
        self.model = model
        self.scheduler = sched.ContinuousBatchingScheduler(
            num_blocks=num_blocks, block_size=block_size,
            max_slots=max_slots, max_blocks_per_seq=max_blocks_per_seq,
        )
        self.max_slots = max_slots
        # decode-table width: with no per-sequence cap, a sequence can at
        # most hold the whole non-null pool
        self.table_width = max_blocks_per_seq or (num_blocks - 1)
        self.eos_id = eos_id
        self.step_count = 0
        self.completed: dict[int, tuple] = {}  # rid -> generated tokens
        # snapshot a victim's pages to host BEFORE the scheduler frees the
        # ledger entries (the resume half restores them bitwise)
        orig_preempt = self.scheduler.preempt

        def _preempt(seq, step):
            seq.saved_payload = self.model.save_blocks(seq, list(seq.blocks))
            orig_preempt(seq, step)

        self.scheduler.preempt = _preempt

    @classmethod
    def with_model(cls, cfg, params, *, num_blocks=64, block_size=16,
                   max_slots=8, max_blocks_per_seq=16, precision=None,
                   impl=None, mesh=None, eos_id=None):
        model = PagedModel(
            cfg, params, num_blocks=num_blocks, block_size=block_size,
            max_slots=max_slots, max_blocks_per_seq=max_blocks_per_seq,
            precision=precision, impl=impl, mesh=mesh,
        )
        return cls(model, num_blocks=num_blocks, block_size=block_size,
                   max_slots=max_slots, max_blocks_per_seq=max_blocks_per_seq,
                   eos_id=eos_id)

    def submit(self, req: Request) -> None:
        self.scheduler.submit(req)

    # -- one step of virtual time -------------------------------------------

    def step(self) -> int:
        """Admissions + one decode over all slots, in a ``serve.step``
        span. Returns the number of live tokens produced this step."""
        sc = self.scheduler
        queued = len(sc.pending) + sum(map(len, sc.queues.values()))
        with step_span(self.step_count, running=len(sc.running),
                       queued=queued):
            return self._step()

    def _step(self) -> int:
        s = self.step_count
        sc = self.scheduler
        for seq in sc.admit(s):
            if seq.saved_payload is not None:  # resume: restore pages
                self.model.restore_blocks(seq, seq.blocks, seq.saved_payload)
                seq.saved_payload = None
                if hasattr(self.model, "sync_table"):
                    self.model.sync_table(seq)
            else:
                first = self.model.prefill(seq, seq.blocks)
                sc.record_token(seq, first)
                if sc.should_retire(seq, self.eos_id):
                    self._retire(seq, s)

        # grow blocks (preempting on exhaustion) for this step's writes
        skipped: set[int] = set()
        for slot in sorted(self.scheduler.running):
            seq = self.scheduler.running.get(slot)
            if seq is None:  # already preempted as someone's victim
                continue
            before = len(seq.blocks)
            if not sc.ensure_block(seq, s):
                skipped.add(seq.rid)  # preempted itself; decode next round
                continue
            if len(seq.blocks) != before and hasattr(self.model,
                                                     "sync_table"):
                self.model.sync_table(seq)

        produced = 0
        if self.scheduler.running:
            tokens = np.zeros(self.max_slots, np.int64)
            positions = np.zeros(self.max_slots, np.int64)
            tables = np.full(
                (self.max_slots, self.table_width), NULL_BLOCK, np.int32,
            )
            if hasattr(self.model, "tables"):
                tables = self.model.tables
                tables[:] = NULL_BLOCK
            active = np.zeros(self.max_slots, bool)
            live = dict(self.scheduler.running)
            for slot, seq in live.items():
                active[slot] = True
                tokens[slot] = seq.generated[-1]
                positions[slot] = seq.next_position()
                if hasattr(self.model, "sync_table"):
                    self.model.sync_table(seq)
                else:
                    tables[slot, : len(seq.blocks)] = seq.blocks
            next_tokens = self.model.decode(tokens, positions, tables, active)
            for slot, seq in live.items():
                sc.record_token(seq, int(next_tokens[slot]))
                produced += 1
                if sc.should_retire(seq, self.eos_id):
                    self._retire(seq, s)

        self.step_count += 1
        return produced

    def _retire(self, seq, step: int) -> None:
        self.scheduler.retire(seq, step)
        self.completed[seq.rid] = tuple(seq.generated)

    def run(self, max_steps: int = 10_000) -> dict:
        while not self.scheduler.idle():
            if self.step_count >= max_steps:
                raise RuntimeError(
                    f"engine did not drain in {max_steps} steps "
                    f"(running={sorted(s.rid for s in self.scheduler.running.values())})"
                )
            self.step()
        return dict(self.completed)

    def leaked_blocks(self) -> int:
        return self.scheduler.leaked_blocks()
