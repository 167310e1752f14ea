"""Slot-based continuous batching: requests join and leave the decode
batch at token boundaries.

The reference's serving design is one-request-at-a-time TF-Serving
behind an HTTP proxy (`/root/reference/docs_dev/tf_serving.md:1-60`,
`testing/test_tf_serving.py`); its only batching lever is client-side.
The window `Batcher` (server.py) already improves on that, but a late
arrival still waits for the whole in-flight generation, and one short
request in a group waits for its longest neighbor.

This module is the TPU-idiomatic fix (the JetStream pattern): keep ONE
compiled decode step over a fixed `[slots]` batch alive and make
admission DATA, not shape —

- A new request takes a free slot FROZEN (`ContinuousEngine._adopt`,
  slot index traced ⇒ one compile total) and its prompt is fed in
  fixed-size slices straight through the slot's block table
  (`_append_rows`, one `[1, s]` program for every prompt length),
  interleaved with the other slots' decode steps.
- Every decode step advances ALL slots at once at per-slot cursors
  (`SlotState.length` is a vector where `DecodeState.length` is a
  scalar); a request exits the moment IT hits EOS or its own max_new,
  freeing the slot for the next arrival at the very next token.
- Freed slots keep computing garbage — static shapes are the TPU
  contract, and a masked-out row costs the same as the Batcher's dummy
  rows. Decode is HBM-bound (each step reads every weight once for the
  whole batch), so a wasted row is ~free; an idle CHIP between window
  groups is not.

Model math is shared with the engine via `engine.transformer_block`
(norms/projections/rotary/MLP injected with this module's per-row
scatter write + per-row masks), so the two serving paths cannot drift.

KV memory is PAGED (the vLLM/SGLang move): instead of a dense
[L, S, max_len] buffer, slots address a shared pool of fixed-size
blocks through per-slot block tables, decode gathers K/V through the
table (`ops.paged_attention`), and prompt slices are written at the
row's cursor with no padding — cell index == token position, so a
block's content is a pure function of its token prefix. That canonical
form feeds the automatic RADIX PREFIX CACHE (serving/paged.py): prompt
blocks are indexed by token prefix at admission and donated back to
the tree at retirement, and a new request reuses every cached cell it
shares with ANY earlier one, prefilling only its suffix. The one-shot
`InferenceEngine` keeps its dense cache — batch-1 generate has no
sharing to exploit.
"""

from __future__ import annotations

import asyncio
import collections
import os
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.ops.attention import (
    dot_product_attention,
    paged_attention,
    paged_prefill_attention,
    pool_cell_shape,
    resolve_paged_attention_impl,
    resolve_paged_prefill_impl,
)
from kubeflow_tpu.ops.norms import rms_norm
from kubeflow_tpu.ops.pallas.flash_attention import resolve_interpret
from kubeflow_tpu.ops.pallas.paged_attention import group_blocks
from kubeflow_tpu.ops.rotary import rope_frequencies
from kubeflow_tpu.serving.engine import (
    InferenceEngine,
    RecurrentState,
    SamplingParams,
    mamba_block,
    scan_layers,
    transformer_block,
)
from kubeflow_tpu.obs.cachestats import CacheLedger
from kubeflow_tpu.obs.compiles import startup_span
from kubeflow_tpu.obs.profiling import CompileWatch, PhaseProfiler
from kubeflow_tpu.obs.timeline import RequestTimeline, TimelineStore
from kubeflow_tpu.serving import migration
from kubeflow_tpu.serving.paged import (BlockPool, HostSpillTier,
                                        RadixPrefixCache)
from kubeflow_tpu.serving.speculative import _dist, _draw
from kubeflow_tpu.tenancy.ledger import TenantLedger
from kubeflow_tpu.tenancy.scheduler import FairShareQueue, ReqMeta


# Prompt tokens a prefill slice feeds, where the caller names no other
# budget: the one value every chip run has used (PERF.md).
PREFILL_CHUNK_TOKENS = 256


def pow2_ceil(n: int) -> int:
    """Smallest power of two >= n (n >= 1) — the law `reset_slots`
    pads its slot list by (one compiled program per pow2 size, not per
    novel count)."""
    p = 1
    while p < n:
        p *= 2
    return p


def bucket_pow2(n: int, cap: int) -> int:
    """Round up to a power of two (>= 16), capped — bounded compile
    shapes instead of one compile per novel length. Shared by the
    window Batcher and the continuous engine's draft prefill."""
    return min(max(pow2_ceil(n), 16), cap)


class SlotState:
    """Per-slot KV cache + cursors, a pytree (jit-carryable).

    The decode-batch analog of `engine.DecodeState`, with every cursor
    widened to a per-slot vector: slots sit at DIFFERENT sequence
    positions, which is the whole point of continuous batching.
    """

    def __init__(self, k, v, length, tok, aid=None,
                 block_table=None, frozen=None, rec=None):
        # the paged pool, [L, num_blocks, block_size, *cell]: block 0 is
        # the trash block; a cell (one token's K, or V, of one layer) is
        # [n_kv, hd] at heads of whole lane tiles and [1, n_kv * hd] at
        # smaller ones (ops.attention.pool_cell_shape)
        self.k = k
        self.v = v
        self.length = length  # [S] int32 — filled cache cells per row
        self.tok = tok        # [S] int32 — last sampled token per row
        if aid is None:       # multi-LoRA adapter id (0 = plain base)
            aid = jnp.zeros(length.shape, jnp.int32)
        self.aid = aid        # [S] int32
        # [S, blocks_per_slot] int32 — physical block per logical block.
        # Cell c of slot s lives at pool[:, table[s, c // bs], c % bs]:
        # the paged indirection that lets slots share prefix blocks and
        # frees HBM accounting from the dense S * max_len worst case.
        self.block_table = block_table
        # [S] bool — mid-chunked-prefill rows. A frozen row rides along
        # in decode/speculative dispatches but is fully masked there:
        # its KV writes are routed to the trash block and its cursors
        # (length, tok) never move — only `append_rows` advances it.
        if frozen is None:
            frozen = jnp.zeros(length.shape, bool)
        self.frozen = frozen
        # The second cache kind, beside the pool: the recurrent state
        # of a model with Mamba layers, one row a slot and not paged —
        # `RecurrentState(conv [Lm, S, K - 1, C], ssm [Lm, S, H, P,
        # N])`, zeroed when a slot is adopted, advanced by append and
        # decode, still while the row is frozen or idle. None (no
        # leaves: the same programs as before) for every other model.
        self.rec = rec

    def replace(self, **fields) -> "SlotState":
        """This state with `fields` changed."""
        return SlotState(**{**vars(self), **fields})

    def tree_flatten(self):
        return (self.k, self.v, self.length, self.tok, self.aid,
                self.block_table, self.frozen, self.rec), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    SlotState, SlotState.tree_flatten, SlotState.tree_unflatten
)


class DraftSlots:
    """Per-slot DRAFT-model KV cache for continuous speculative
    decoding, a pytree (jit-carryable).

    The draft cache stays DENSE ([L, S, draft_max_len, n_kv, hd]) where
    the target cache is paged: the draft model is small by design, so
    its cache is a rounding error next to the target pool, and paging
    it would add a second block table to every rollback. Rows are
    compacted like the target's (cell index == logical position, offset
    0), and `length` tracks the TARGET row's cursor exactly — after
    every speculative round both caches agree on how many tokens are
    committed, which is the whole rollback contract."""

    def __init__(self, k, v, length):
        self.k = k            # [L, S, W_draft, n_kv_d, hd_d]
        self.v = v
        self.length = length  # [S] int32 — committed cells per row

    def tree_flatten(self):
        return (self.k, self.v, self.length), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    DraftSlots, DraftSlots.tree_flatten, DraftSlots.tree_unflatten
)


class ContinuousEngine:
    """Device half of continuous batching for one `InferenceEngine`.

    A few compiled programs, all shape-stable for the server's life:
    `_adopt` (slot index is traced data), `_append_rows` (one prompt
    slice through the block table) and `_step` (one token for all S
    slots). The host half (`ContinuousBatcher`) owns admission,
    budgets, and EOS retirement — policies live in Python, tensors on
    device.
    """

    @startup_span("startup.engine")
    def __init__(self, engine: InferenceEngine, max_slots: int = 8,
                 block_size: int = 64, num_blocks: int | None = None,
                 paged_attention_impl: str = "auto",
                 pool: BlockPool | None = None,
                 draft: InferenceEngine | None = None):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        self.engine = engine
        if draft is not None:
            # continuous speculative decoding (ISSUE 9): the accept
            # rule compares draft and target distributions tokenwise,
            # and the draft cache row mirrors the target row cursor
            if draft.cfg.vocab_size != engine.cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft.cfg.vocab_size} != target "
                    f"vocab {engine.cfg.vocab_size}")
            if draft.ec.max_len < engine.ec.max_len:
                raise ValueError(
                    f"draft max_len {draft.ec.max_len} < target "
                    f"max_len {engine.ec.max_len}: the draft cache row "
                    "must cover every target cursor position")
            if engine.adapter_pack is not None:
                raise ValueError(
                    "speculative decoding does not compose with a "
                    "multi-LoRA adapter pack (the verify pass would "
                    "score base-model logits against adapter rows)")
        self.draft = draft
        # A model with recurrent layers keeps a second cache kind, a
        # state per slot (`SlotState.rec`). What rests on a sequence's
        # cache being KV cells alone is refused here, in one place: a
        # block of cells cannot seed, move or restore a sequence whose
        # recurrent layers have no state at that boundary, and a
        # rejected draft token cannot be taken back out of a state.
        self.recurrent = engine.family.recurrent
        if draft is not None:
            self.refuse_recurrent(
                "a draft model (speculative decoding rolls rejected "
                "tokens back, and a recurrent state cannot be rolled back)")
        if engine.adapter_pack is not None:
            self.refuse_recurrent(
                "an adapter pack (multi-LoRA wraps the attention "
                "block's matmuls only)")
        if block_size < 2 or block_size & (block_size - 1):
            raise ValueError(
                f"block_size must be a power of two >= 2, got {block_size}")
        # Resolve the attention impl ONCE at construction (validates
        # the name too): the decode closure passes it through every
        # trace, and serving labels its metrics with the resolved
        # value. "auto" = pallas on TPU, xla elsewhere.
        self.paged_attention_impl = paged_attention_impl
        self.attention_impl = resolve_paged_attention_impl(
            paged_attention_impl, head_dim=engine.cfg.head_dim)
        # chunked-prefill / draft-verify writes go through the fused
        # prefill/append op — same knob. This is the platform's answer;
        # each trace re-resolves the REQUEST with its chunk shape,
        # because "auto" also bounds the kernel's VMEM need.
        self.prefill_impl = resolve_paged_prefill_impl(
            paged_attention_impl, head_dim=engine.cfg.head_dim)
        if "pallas" in (self.attention_impl, self.prefill_impl):
            # fail at construction, not at the first request's trace
            resolve_interpret(None)
        self.S = max_slots
        # Paged KV geometry. The cache is a POOL of fixed-size blocks
        # [L, num_blocks, block_size, *cell] (`init_slots`) plus a
        # per-slot block table; block 0 is the reserved trash block
        # (unallocated table entries point there, so a retired-but-unreset
        # slot's garbage writes land harmlessly). The default pool is the dense
        # equivalent (every slot can hold max_len) — shrink num_blocks
        # to cap KV HBM below S * max_len when real requests are short.
        self.block_size = block_size
        # a cell's form (rows, lanes) follows the head size
        self.kv_cell = pool_cell_shape(engine.cfg.num_kv_heads,
                                       engine.cfg.head_dim)
        self.blocks_per_slot = -(-engine.ec.max_len // block_size)
        self.kv_width = self.blocks_per_slot * block_size
        if num_blocks is None:
            num_blocks = (pool.num_blocks if pool is not None
                          else 1 + max_slots * self.blocks_per_slot)
        if num_blocks < 1 + self.blocks_per_slot:
            raise ValueError(
                f"num_blocks {num_blocks} < {1 + self.blocks_per_slot} "
                f"(trash + one slot's worth at max_len "
                f"{engine.ec.max_len} / block_size {block_size}): a "
                "single max-length request could never be admitted")
        self.num_blocks = num_blocks
        if pool is not None:
            # A caller-supplied pool must agree with the geometry
            # `ops.paged_attention` will see (tables/masks are laid out
            # in `blocks_per_slot * block_size` cells over a
            # `[num_blocks, block_size]` pool). A mismatch used to
            # surface only as an opaque gather/reshape shape error deep
            # inside jit on the first decode step.
            if (pool.block_size != block_size
                    or pool.num_blocks != num_blocks):
                raise ValueError(
                    f"BlockPool geometry (num_blocks="
                    f"{pool.num_blocks}, block_size={pool.block_size}) "
                    f"does not match the engine's paged-attention "
                    f"layout (num_blocks={num_blocks}, block_size="
                    f"{block_size}, blocks_per_slot="
                    f"{self.blocks_per_slot}): block tables and KV "
                    f"masks would disagree with the pool shape")
            self.pool = pool
        else:
            self.pool = BlockPool(num_blocks, block_size)
        # KV buffers dominate serving HBM: donate the old state so step
        # and append update in place instead of holding two copies
        # (same policy as the Trainer's donated TrainState). The
        # adapter pack rides as an ARGUMENT, not a closure — closed-over
        # arrays bake into the lowered module as constants (see the
        # params note in engine.InferenceEngine.__init__).
        self._step_jit = jax.jit(self._step, donate_argnums=(2,),
                                 static_argnames=("steps",))
        self._reset_jit = jax.jit(self._reset_slots, donate_argnums=(0,))
        # migration (serving/migration.py): export gathers block
        # payloads without touching the state; import scatters them in
        # place (donated, like append/step — KV dominates serving HBM)
        self._export_jit = jax.jit(self._export_blocks)
        self._import_jit = jax.jit(self._import_blocks,
                                   donate_argnums=(0,))
        # admission: adopt points a frozen slot at its planned blocks,
        # copy_cells seeds a partial CoW block, and append_rows feeds
        # budget-size prompt slices through the fused prefill/append
        # path between decode chunks
        self._append_jit = jax.jit(self._append_rows,
                                   donate_argnums=(2,))
        self._adopt_jit = jax.jit(self._adopt, donate_argnums=(0,))
        self._copy_cells_jit = jax.jit(self._copy_cells,
                                       donate_argnums=(0,))
        if draft is not None:
            self._spec_draft_jit = jax.jit(
                self._spec_draft, donate_argnums=(1,),
                static_argnames=("gamma",))
            self._spec_verify_jit = jax.jit(
                self._spec_verify, donate_argnums=(2, 3),
                static_argnames=("gamma",))
            self._dinsert_jit = jax.jit(self._draft_insert,
                                        donate_argnums=(0,))

    def refuse_recurrent(self, what: str) -> None:
        """Raise where the model has recurrent layers and `what` was
        asked of it."""
        if self.recurrent:
            raise ValueError(
                f"{self.engine.family.name} has recurrent layers, whose "
                f"state lives in the slot and not in the paged pool: it "
                f"cannot be served with {what}")

    def _inv_freq(self):
        cfg = self.engine.cfg
        if not self.engine.family.rotary:
            return None
        return rope_frequencies(cfg.head_dim, theta=cfg.rope_theta)

    # -- state ------------------------------------------------------------

    def init_slots(self) -> SlotState:
        cfg = self.engine.cfg
        shape = (self.engine.kv_layers, self.num_blocks, self.block_size,
                 *self.kv_cell)
        rec = None
        if self.recurrent:
            lm = self.engine.mamba_layers
            rec = RecurrentState(
                jnp.zeros((lm, self.S, cfg.mamba_d_conv - 1, cfg.conv_dim),
                          cfg.state_dtype),
                jnp.zeros((lm, self.S, cfg.mamba_n_heads, cfg.mamba_d_head,
                           cfg.mamba_d_state), cfg.state_dtype))
        return SlotState(
            jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype),
            jnp.zeros((self.S,), jnp.int32),
            jnp.zeros((self.S,), jnp.int32),
            None,
            jnp.zeros((self.S, self.blocks_per_slot), jnp.int32),
            rec=rec,
        )

    def state_bytes_per_slot(self) -> int:
        """HBM bytes of one slot's recurrent state, all Mamba layers:
        what a decode step reads and writes once for every slot that
        decodes in it. 0 for a model without recurrent layers."""
        if not self.recurrent:
            return 0
        cfg = self.engine.cfg
        cells = ((cfg.mamba_d_conv - 1) * cfg.conv_dim
                 + cfg.mamba_n_heads * cfg.mamba_d_head * cfg.mamba_d_state)
        return (self.engine.mamba_layers * cells
                * jnp.dtype(cfg.state_dtype).itemsize)

    def decode_kv_steps(self, cursors) -> dict[str, int]:
        """What a decode step at these cursors (one per decoding slot)
        asks of the KV pool, in the paged decode kernel's units
        (ops/pallas/paged_attention.py): the pool blocks the rows can
        see, the grid steps those fall in (a step owns `group_blocks`
        consecutive blocks of a row and fetches only when one is
        live), and the grid's size, slots x groups, which is static."""
        cfg = self.engine.cfg
        bs, nb = self.block_size, self.blocks_per_slot
        g = group_blocks(nb, bs, cfg.num_kv_heads, cfg.head_dim,
                         jnp.dtype(cfg.dtype).itemsize)
        window = getattr(cfg, "sliding_window", None)
        live = fetching = 0
        for cur in cursors:
            lo = max(cur - window + 1, 0) // bs if window else 0
            hi = min(cur // bs, nb - 1)
            live += hi - lo + 1
            fetching += hi // g - lo // g + 1
        return {"kv_blocks_live": live, "kv_steps_fetching": fetching,
                "kv_steps": self.S * -(-nb // g)}

    def kv_block_bytes(self) -> int:
        """HBM bytes one pool block holds (K+V, all layers) — the unit
        `serving_kv_blocks_in_use` and bench_decode_paged report in."""
        cfg = self.engine.cfg
        itemsize = jnp.dtype(cfg.dtype).itemsize
        return (2 * self.engine.kv_layers * self.block_size
                * cfg.num_kv_heads * cfg.head_dim * itemsize)

    def _reset_slots(self, st: SlotState, slots):
        """Point retired slots back at the trash block and zero their
        cursors. Ordered after the slots' last in-flight decode chunk
        by the donation chain, this guarantees a freed block sees no
        further writes once it's re-allocated (or adopted by the radix
        tree) — the paged design's one cross-slot hazard."""
        bt = st.block_table.at[slots].set(0)
        length = st.length.at[slots].set(0)
        frozen = st.frozen.at[slots].set(False)
        return st.replace(length=length, block_table=bt, frozen=frozen)

    def reset_slots(self, st: SlotState, slots: list[int]) -> SlotState:
        """Host entry: pads the slot list to a power of two by
        repeating (idempotent) so compiles stay bounded."""
        n = pow2_ceil(len(slots))
        padded = list(slots) + [slots[-1]] * (n - len(slots))
        return self._reset_jit(st, jnp.asarray(padded, jnp.int32))

    # -- migration --------------------------------------------------------

    def _wire_shape(self, n: int) -> tuple[int, ...]:
        """`n` pool blocks as they leave and enter this engine: a head a
        row, whichever form the pool keeps a cell in."""
        cfg = self.engine.cfg
        return (self.engine.kv_layers, n, self.block_size,
                cfg.num_kv_heads, cfg.head_dim)

    def _export_blocks(self, k_pool, v_pool, ids):
        wire = self._wire_shape(ids.shape[0])
        return k_pool[:, ids].reshape(wire), v_pool[:, ids].reshape(wire)

    def export_blocks(self, st: SlotState, block_ids):
        """Host copies of the K/V payloads held by physical blocks
        `block_ids` — `(k, v)`, each `[L, n, block_size, n_kv, hd]`
        numpy, in id order: the wire form, a head a row whatever form
        the pool keeps a cell in (the gathered blocks are reshaped, not
        the pool). The transfer unit of live sequence migration
        (serving/migration.py): one device gather + one transfer covers
        an arbitrary id list (one cheap compile per list LENGTH). Does
        not touch the state."""
        ids = jnp.asarray(list(block_ids), jnp.int32)
        k, v = self._export_jit(st.k, st.v, ids)
        return np.asarray(k), np.asarray(v)

    def _import_blocks(self, st: SlotState, ids, k, v):
        # wire form -> the pool's cells, on the n blocks
        blocks = st.k.shape[:1] + ids.shape + st.k.shape[2:]
        kp = st.k.at[:, ids].set(k.reshape(blocks).astype(st.k.dtype))
        vp = st.v.at[:, ids].set(v.reshape(blocks).astype(st.v.dtype))
        return st.replace(k=kp, v=vp)

    def import_blocks(self, st: SlotState, block_ids, k, v) -> SlotState:
        """Scatter migrated block payloads into locally-allocated
        blocks `block_ids` (donates `st` — in-place pool update, same
        policy as append/step). Payloads keep the exporter's canonical
        form (cell index == logical token position), so imported
        blocks are immediately radix-shareable. Payloads come in
        `export_blocks`' wire form, `[L, n, block_size, n_kv, hd]`.
        Raises ValueError when the payload shape disagrees with this
        pool's block geometry — a silent shape coercion here would
        corrupt every sequence that later seeds from these blocks."""
        want = self._wire_shape(len(list(block_ids)))
        k = np.asarray(k)
        v = np.asarray(v)
        if tuple(k.shape) != want or tuple(v.shape) != want:
            raise ValueError(
                f"import_blocks: payload shape k={tuple(k.shape)} "
                f"v={tuple(v.shape)} does not match pool block "
                f"geometry [L, n, block_size, n_kv, hd] = {want}")
        return self._import_jit(st,
                                jnp.asarray(list(block_ids), jnp.int32),
                                jnp.asarray(k), jnp.asarray(v))

    # -- decode -----------------------------------------------------------

    def _decode_one(self, params, adapters, st: SlotState,
                    sp: SamplingParams, rng):
        """One decode token for ALL slots at per-slot cursors.

        Mirrors `engine._forward_cached`'s s=1 case with every scalar
        cursor vectorized: rope positions, causal masks and cache
        writes are per-row. Retired slots compute garbage (masked by
        the host); their cursors clamp at max_len so a long-idle slot
        can never scatter out of bounds.
        """
        eng = self.engine
        cfg, fam, ec = eng.cfg, eng.family, eng.ec
        S = self.S
        rng, sub = jax.random.split(rng)

        positions = st.length[:, None]                      # [S, 1]
        inv_freq = self._inv_freq()
        kv_positions = jnp.broadcast_to(
            jnp.arange(self.kv_width, dtype=jnp.int32)[None, :],
            (S, self.kv_width))
        # causal q>=kv masking hides stale cells beyond each row's
        # cursor (a reused slot's old tail)
        write_at = jnp.minimum(st.length, ec.max_len - 1)
        # paged write coordinates: logical cell -> (physical block,
        # offset) through each row's block table. Frozen rows (mid
        # chunked prefill) write to the trash block instead — a decode
        # step must never touch cells `append_rows` will fill.
        rows = jnp.arange(S)
        write_blk = jnp.where(
            st.frozen, 0,
            st.block_table[rows, write_at // self.block_size])
        write_off = write_at % self.block_size

        x = eng._embed(params, st.tok[:, None])

        # Cache as scan CARRY with in-place row scatters — same
        # rationale as engine._forward_cached: ys-stacked cache slices
        # rewrote the whole cache every token, doubling decode HBM
        # traffic. Here the per-step write is S rows per layer.
        def layer(carry, scanned):
            x, k_all, v_all, rec = carry
            if adapters is None:
                p, li = scanned
                proj = None
            else:
                from kubeflow_tpu.serving.multilora import lora_proj
                p, ab, li = scanned
                proj = lora_proj(ab, st.aid,
                                 eng.adapter_pack.scaling, cfg)

            def write_kv(k, v):
                # one [S]-row scatter into the shared block pool:
                # slot s's token lands at (table[s, at//bs], at%bs),
                # its [n_kv, hd] row in the form of the pool's cell.
                # The WHOLE pool goes on to the attention call: a
                # layer's slice taken here would be a copy of the
                # layer's pool a layer on the chip (268 MB at Mistral's
                # cell: PERF.md, PR 26)
                cell = (S, *self.kv_cell)
                return (k_all.at[li, write_blk, write_off].set(
                            k[:, 0].reshape(cell).astype(k_all.dtype)),
                        v_all.at[li, write_blk, write_off].set(
                            v[:, 0].reshape(cell).astype(v_all.dtype)))

            def attn(q, kp, vp):
                # kp/vp are every layer's block POOL and `li` says
                # which to read; the paged path gathers each row's K/V
                # through its block table. Cell index == logical
                # token position, so masking semantics (and bits — see
                # paged_attention's docstring) match the dense path.
                return paged_attention(
                    q, kp, vp, st.block_table, positions, kv_positions,
                    causal=True, kv_mask=None,
                    window=getattr(cfg, "sliding_window", None),
                    layer=li, impl=self.attention_impl)

            x, (k_all, v_all) = transformer_block(
                cfg, fam, p, x, positions, inv_freq, write_kv, attn,
                proj)
            return (x, k_all, v_all, rec), None

        # A recurrent layer moves a row's state by its one token, in
        # place in the carry, as the pool is written. A frozen row
        # (its prompt is still being fed by `append_rows`) and an idle
        # one (no request: its table points at the trash block) stand
        # still: for them the token does not count.
        counts = jnp.where(
            st.frozen | (st.block_table[:, 0] == 0), 0, 1)

        def mamba_layer(carry, scanned):
            x, k_all, v_all, rec = carry
            p, li = scanned
            x, rec = mamba_block(cfg, fam, p, x, rec, li, None, counts)
            return (x, k_all, v_all, rec), None

        x, k_new, v_new, rec = scan_layers(
            cfg, fam, params, (x, st.k, st.v, st.rec), layer,
            mamba_layer, adapters)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = eng._head(params, x[:, -1])
        nxt, lp = eng._sample(logits, sub, sp)
        # frozen rows keep their cursors: length marks the prefilled
        # frontier and tok the NEXT prompt token — a decode step's
        # garbage sample must not clobber either
        st = st.replace(
            k=k_new, v=v_new,
            length=jnp.where(st.frozen, st.length,
                             jnp.minimum(st.length + 1, ec.max_len)),
            tok=jnp.where(st.frozen, st.tok, nxt.astype(jnp.int32)),
            rec=rec)
        return st, nxt, lp, rng

    def _step(self, params, adapters, st: SlotState, sp: SamplingParams,
              rng, *, steps: int):
        """`steps` decode tokens for all slots in ONE dispatch (a
        lax.scan over `_decode_one`) — chunking amortizes per-token
        host dispatch; admission happens between dispatches, so a
        queued request waits at most steps-1 tokens for a freed slot
        (the host's worker chooses steps). The token sequence is
        IDENTICAL for any chunking — the scan body is the single-step
        program, and retirement only changes what the host keeps,
        never what the device computes."""

        def body(carry, _):
            st, rng = carry
            st, tok, lp, rng = self._decode_one(params, adapters, st,
                                                sp, rng)
            return (st, rng), (tok, lp)

        (st, rng), (toks, lps) = jax.lax.scan(
            body, (st, rng), None, length=steps)
        return (st, jnp.moveaxis(toks, 0, 1),
                jnp.moveaxis(lps, 0, 1), rng)  # [S, steps] each

    def step(self, st: SlotState, sp: SamplingParams, rng,
             steps: int = 1):
        """-> (state, tokens [S, steps], logprobs [S, steps], rng)."""
        pack = self.engine.adapter_pack
        return self._step_jit(self.engine.params,
                              None if pack is None else pack.blocks,
                              st, sp, rng, steps=steps)

    # -- chunked prefill (fused paged append) -----------------------------

    def _paged_forward(self, params, adapters, st: SlotState, slots,
                       tokens, n_valid, start):
        """Forward `[g, s]` tokens for slot rows `slots` THROUGH the
        paged pool: each layer's K/V projections are written into the
        rows' block tables at cells [start, start + n_valid) and
        attended in the same fused op (ops.paged_prefill_attention).
        Shared by chunked prefill (`_append_rows`) and the speculative
        verify pass (`_spec_verify`) so the two paths cannot drift.
        Returns (final-norm hidden states [g, s, D], the state with
        the pools, and a recurrent model's rows, written).

        Write disjointness holds by construction: a row only ever
        writes cells at/above its own cursor, which land in its
        exclusively-owned fresh blocks — radix-shared blocks all sit
        strictly below the cursor (see the kernel's docstring)."""
        eng = self.engine
        cfg, fam = eng.cfg, eng.family
        table = st.block_table[slots]
        aid = st.aid[slots]
        s = tokens.shape[1]
        positions = (start[:, None]
                     + jnp.arange(s, dtype=jnp.int32)[None, :])
        inv_freq = self._inv_freq()
        x = eng._embed(params, tokens)

        def layer(carry, scanned):
            x, k_all, v_all, rec = carry
            if adapters is None:
                p, li = scanned
                proj = None
            else:
                from kubeflow_tpu.serving.multilora import lora_proj
                p, ab, li = scanned
                proj = lora_proj(ab, aid, eng.adapter_pack.scaling, cfg)
            cell = {}

            def write_kv(k, v):
                # defer the write: the fused op scatters K/V through
                # the block table and attends in one pass, over the
                # carry itself (every layer's pool, never a slice)
                cell["new"] = (k, v)
                return k_all, v_all

            def attn(q, kp, vp):
                kn, vn = cell["new"]
                # the op's pools are the carry with layer `li`'s
                # visited blocks rewritten in place
                out, cell["k"], cell["v"] = paged_prefill_attention(
                    q, kn, vn, kp, vp, table, start, n_valid,
                    kv_mask=None,
                    window=getattr(cfg, "sliding_window", None),
                    layer=li, impl=self.paged_attention_impl)
                return out

            x, _ = transformer_block(
                cfg, fam, p, x, positions, inv_freq, write_kv, attn,
                proj)
            return (x, cell["k"], cell["v"], rec), None

        # A recurrent layer carries the rows' state in and out of the
        # slice: in from the slots' rows of the carry, out to them
        # again after the slice's valid tokens.
        def mamba_layer(carry, scanned):
            x, k_all, v_all, rec = carry
            p, li = scanned
            x, rec = mamba_block(cfg, fam, p, x, rec, li, slots, n_valid)
            return (x, k_all, v_all, rec), None

        x, k_new, v_new, rec = scan_layers(
            cfg, fam, params, (x, st.k, st.v, st.rec), layer,
            mamba_layer, adapters)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, st.replace(k=k_new, v=v_new, rec=rec)

    def _append_rows(self, params, adapters, st: SlotState, slots,
                     tokens, n_valid, finish, sp, rng):
        """One chunked-prefill slice: feed `tokens[i, :n_valid[i]]` of
        each listed slot's remaining prompt through the paged pool,
        advancing the row cursor by n_valid. Rows with `finish` sample
        their first output token and unfreeze; others stay frozen (the
        decode step keeps masking them). Padding rows (a repeated slot
        with n_valid 0) are no-ops: `.add(0)` moves nothing and their
        sampled token is discarded by `finish=False`."""
        eng, ec = self.engine, self.engine.ec
        rng, sub = jax.random.split(rng)
        start = st.length[slots]
        x, st = self._paged_forward(
            params, adapters, st, slots, tokens, n_valid, start)
        last = jnp.maximum(n_valid - 1, 0)
        x_last = jnp.take_along_axis(
            x, last[:, None, None], axis=1)[:, 0]
        logits = eng._head(params, x_last)
        sp_rows = SamplingParams(temperature=sp.temperature[slots],
                                 top_k=sp.top_k[slots],
                                 top_p=sp.top_p[slots])
        nxt, lp = eng._sample(logits, sub, sp_rows)
        length = jnp.minimum(st.length.at[slots].add(n_valid),
                             ec.max_len)
        newtok = jnp.where(finish, nxt.astype(jnp.int32),
                           st.tok[slots])
        tok = st.tok.at[slots].set(newtok)
        frozen = st.frozen.at[slots].set(
            jnp.where(finish, False, st.frozen[slots]))
        return (st.replace(length=length, tok=tok, frozen=frozen),
                nxt, lp, rng)

    def append_rows(self, st: SlotState, slots, tokens, n_valid,
                    finish, sp: SamplingParams, rng):
        """Host entry for one chunked-prefill slice. -> (state,
        first_token [g], logprob [g], rng); first_token/logprob are
        only meaningful for rows with finish=True."""
        pack = self.engine.adapter_pack
        return self._append_jit(
            self.engine.params,
            None if pack is None else pack.blocks,
            st, jnp.asarray(slots, jnp.int32),
            jnp.asarray(tokens, jnp.int32),
            jnp.asarray(n_valid, jnp.int32),
            jnp.asarray(finish, bool), sp, rng)

    def _adopt(self, st: SlotState, slot, table, seed_len, tok, aid):
        """Point `slot` at its planned block `table` with `seed_len`
        cells already seeded from the radix cache, FROZEN for chunked
        prefill: decode steps mask the row until `append_rows` has fed
        the whole suffix. `tok` is the next prompt token (kept for the
        cursor invariant; append feeds tokens explicitly)."""
        rec = st.rec
        if rec is not None:
            # a recurrent layer's state starts from zero: nothing of
            # the slot's last request may reach this one
            rec = RecurrentState(rec.conv.at[:, slot].set(0),
                                 rec.ssm.at[:, slot].set(0))
        return st.replace(
            length=st.length.at[slot].set(seed_len),
            tok=st.tok.at[slot].set(tok),
            aid=st.aid.at[slot].set(aid),
            block_table=st.block_table.at[slot].set(table),
            frozen=st.frozen.at[slot].set(True), rec=rec)

    def adopt_slot(self, st: SlotState, slot: int, table, seed_len: int,
                   tok: int, aid: int = 0) -> SlotState:
        return self._adopt_jit(
            st, jnp.asarray(slot, jnp.int32),
            jnp.asarray(table, jnp.int32),
            jnp.asarray(seed_len, jnp.int32),
            jnp.asarray(tok, jnp.int32), jnp.asarray(aid, jnp.int32))

    def _copy_cells(self, st: SlotState, src, dst, n):
        """Copy cells [0, n) of pool block `src` into block `dst` —
        the copy half of copy-on-write for a partially-matched radix
        block: the new request seeds its own fresh block from the
        shared one and diverges there."""
        i = jnp.arange(self.block_size)
        sel = (i < n)[None, :, None, None]
        kd = jnp.where(sel, st.k[:, src], st.k[:, dst])
        vd = jnp.where(sel, st.v[:, src], st.v[:, dst])
        return st.replace(k=st.k.at[:, dst].set(kd),
                          v=st.v.at[:, dst].set(vd))

    def copy_cells(self, st: SlotState, src: int, dst: int,
                   n: int) -> SlotState:
        return self._copy_cells_jit(
            st, jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
            jnp.asarray(n, jnp.int32))

    # -- speculative decoding on paged KV ---------------------------------

    def init_draft_slots(self) -> DraftSlots:
        cfg = self.draft.cfg
        shape = (cfg.num_layers, self.S, self.draft.ec.max_len,
                 cfg.num_kv_heads, cfg.head_dim)
        return DraftSlots(
            jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype),
            jnp.zeros((self.S,), jnp.int32))

    def _draft_decode_one(self, dparams, dst: DraftSlots, feed):
        """One draft-model token for ALL slots against the dense
        per-slot draft cache (the draft-side mirror of `_decode_one`).
        Cell index == position, so causal masking alone hides stale
        tail cells; every cell is written before it is first attended."""
        deng = self.draft
        cfg, fam = deng.cfg, deng.family
        W = deng.ec.max_len
        S = self.S
        positions = dst.length[:, None]
        inv_freq = rope_frequencies(cfg.head_dim, theta=cfg.rope_theta)
        kv_positions = jnp.broadcast_to(
            jnp.arange(W, dtype=jnp.int32)[None, :], (S, W))
        write_at = jnp.minimum(dst.length, W - 1)
        rows = jnp.arange(S)
        x = deng._embed(dparams, feed[:, None])

        def layer(carry, scanned):
            x, k_all, v_all = carry
            p, li = scanned
            cell = {}

            def write_kv(k, v):
                k2 = k_all.at[li, rows, write_at].set(
                    k[:, 0].astype(k_all.dtype))
                v2 = v_all.at[li, rows, write_at].set(
                    v[:, 0].astype(v_all.dtype))
                cell["k"], cell["v"] = k2, v2
                return (jax.lax.dynamic_index_in_dim(
                            k2, li, 0, keepdims=False),
                        jax.lax.dynamic_index_in_dim(
                            v2, li, 0, keepdims=False))

            def attn(q, kp, vp):
                return dot_product_attention(
                    q, kp, vp, positions, kv_positions, causal=True,
                    window=getattr(cfg, "sliding_window", None),
                    contiguous_positions=True)

            x, _ = transformer_block(
                cfg, fam, p, x, positions, inv_freq, write_kv, attn,
                None)
            return (x, cell["k"], cell["v"]), None

        layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        (x, k_new, v_new), _ = jax.lax.scan(
            layer, (x, dst.k, dst.v), (dparams["blocks"], layer_ids))
        x = rms_norm(x, dparams["final_norm"], cfg.norm_eps)
        logits = deng._head(dparams, x[:, -1])
        dst = DraftSlots(k_new, v_new,
                         jnp.minimum(dst.length + 1, W))
        return dst, logits

    def _draft_insert(self, dst: DraftSlots, slot, pstate, npad):
        """Compact row 0 of a batch-1 draft prefill `DecodeState` into
        draft-cache row `slot` (bucket left-pads stripped: cell index
        == token position, as in the target's pool)."""
        W = self.draft.ec.max_len
        j = jnp.arange(W, dtype=jnp.int32)
        src = jnp.minimum(j + npad, W - 1)
        ck = jnp.take(pstate.k[:, 0], src, axis=1)
        cv = jnp.take(pstate.v[:, 0], src, axis=1)
        return DraftSlots(
            dst.k.at[:, slot].set(ck.astype(dst.k.dtype)),
            dst.v.at[:, slot].set(cv.astype(dst.v.dtype)),
            dst.length.at[slot].set(
                (pstate.length - npad).astype(jnp.int32)))

    def draft_prefill(self, dst: DraftSlots, slot: int,
                      tokens: list[int], rng):
        """Seed draft-cache row `slot` with `tokens`' KV (one draft
        prefill dispatch + one compacting scatter). -> (dst, rng)."""
        deng = self.draft
        b = max(bucket_pow2(len(tokens), deng.ec.max_len), len(tokens))
        arr = np.zeros((1, b), np.int32)
        mask = np.zeros((1, b), bool)
        arr[0, b - len(tokens):] = tokens
        mask[0, b - len(tokens):] = True
        sp, rng = deng._resolve_sampling(
            np.zeros(1, np.float32), np.zeros(1, np.int64),
            np.ones(1, np.float32), rng, batch=1)
        out = deng._prefill_jit(
            deng.params, jnp.asarray(arr), deng.init_state(1), rng, sp,
            jnp.asarray(mask), adapters=None, adapter_ids=None)
        dst = self._dinsert_jit(dst, jnp.asarray(slot, jnp.int32),
                                out[0],
                                jnp.asarray(b - len(tokens), jnp.int32))
        return dst, rng

    def _spec_draft(self, dparams, dst: DraftSlots, tok, sp, rng, *,
                    gamma):
        """Draft `gamma` tokens per slot autoregressively. Returns
        (dst, drafted [S, gamma], q-dists [S, gamma, V], rng) — the
        full draft distributions ride along for the residual resample
        in `_spec_verify`."""
        rng, sub = jax.random.split(rng)

        def body(carry, r):
            dstate, feed = carry
            dstate, logits = self._draft_decode_one(dparams, dstate,
                                                    feed)
            q = _dist(logits, sp)
            d = _draw(r, q)
            return (dstate, d), (d, q)

        (dst, _), (dts, qts) = jax.lax.scan(
            body, (dst, tok), jax.random.split(sub, gamma))
        return (dst, jnp.moveaxis(dts, 0, 1),
                jnp.moveaxis(qts, 0, 1), rng)

    def spec_draft(self, st: SlotState, dst: DraftSlots,
                   sp: SamplingParams, rng, gamma: int):
        return self._spec_draft_jit(self.draft.params, dst, st.tok,
                                    sp, rng, gamma=gamma)

    def _spec_verify(self, params, dparams, st: SlotState,
                     dst: DraftSlots, drafted, qs, sp, rng, *, gamma):
        """Target-verify the drafted window through the paged pool and
        roll both caches back to the accepted frontier.

        The accept/bonus/residual math is the one-shot
        `SpeculativeEngine._speculate` rule vectorized over slots
        (Leviathan et al.): accept drafted[j] while u*q < p; on full
        acceptance draw the bonus token from the target's gamma-th
        distribution, otherwise resample from the clipped residual
        p - q (all-zero rows fall back to p). Rejected tokens' KV cells
        sit strictly above the rolled-back cursors and are rewritten
        before they can ever be attended — rollback is cursor motion,
        not data motion, which is what makes it CoW-safe: shared radix
        blocks all live below the cursor and are never touched.

        Frozen (mid-chunked-prefill) rows ride along fully masked:
        their cursors and tokens never move, and their verify writes
        land above their prefill frontier where `append_rows` rewrites
        them before first attend."""
        eng = self.engine
        ec = eng.ec
        S = self.S
        rng, r_us, r_x = jax.random.split(rng, 3)
        tin = jnp.concatenate([st.tok[:, None], drafted], axis=1)
        slots = jnp.arange(S, dtype=jnp.int32)
        n_valid = jnp.full((S,), gamma + 1, jnp.int32)
        x, st = self._paged_forward(
            params, None, st, slots, tin, n_valid, st.length)
        all_logits = eng._head(params, x)          # [S, gamma+1, V]
        ps = jax.vmap(lambda lg: _dist(lg, sp),
                      in_axes=1, out_axes=1)(all_logits)
        us = jax.random.uniform(r_us, (S, gamma))
        p_d = jnp.take_along_axis(
            ps[:, :gamma], drafted[..., None], axis=2)[..., 0]
        q_d = jnp.take_along_axis(qs, drafted[..., None], axis=2)[..., 0]
        accept = us * q_d < p_d
        k = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1),
                    axis=1)                        # [S] accepted count
        pk = jnp.take_along_axis(ps, k[:, None, None], axis=1)[:, 0]
        qk = jnp.take_along_axis(
            qs, jnp.minimum(k, gamma - 1)[:, None, None], axis=1)[:, 0]
        resid = jnp.clip(pk - qk, 0.0, None)
        resid = jnp.where(
            jnp.sum(resid, axis=1, keepdims=True) > 0.0, resid, pk)
        dist = jnp.where((k == gamma)[:, None], ps[:, gamma], resid)
        extra = _draw(r_x, dist)                   # [S]
        rows = jnp.arange(S)
        emit = jnp.concatenate(
            [drafted, jnp.zeros((S, 1), jnp.int32)], axis=1)
        emit = emit.at[rows, k].set(extra)
        lsm = jax.nn.log_softmax(all_logits, axis=-1)
        lps = jnp.take_along_axis(lsm, emit[..., None], axis=2)[..., 0]
        length = jnp.where(
            st.frozen, st.length,
            jnp.minimum(st.length + k + 1, ec.max_len))
        tok = jnp.where(st.frozen, st.tok, extra.astype(jnp.int32))
        st = st.replace(length=length, tok=tok)
        # draft rollback: the scan advanced every row by gamma; keep
        # the k+1 cells the accepted tokens fed (capped at gamma),
        # then feed the last drafted token unconditionally — its write
        # only COMMITS (advances length) on full acceptance, otherwise
        # it lands above the kept cursor and is rewritten next round
        dlen = dst.length - gamma + jnp.minimum(k + 1, gamma)
        dst = DraftSlots(dst.k, dst.v, dlen)
        dfed, _ = self._draft_decode_one(dparams, dst,
                                         drafted[:, gamma - 1])
        dst = DraftSlots(dfed.k, dfed.v,
                         jnp.where(k == gamma, dfed.length, dlen))
        return st, dst, emit, lps, k, rng

    def spec_verify(self, st: SlotState, dst: DraftSlots, drafted, qs,
                    sp: SamplingParams, rng, gamma: int):
        """-> (state, draft state, emitted [S, gamma+1], logprobs
        [S, gamma+1], accepted counts [S], rng). Row i's valid emitted
        tokens are emit[i, :k[i] + 1]."""
        return self._spec_verify_jit(
            self.engine.params, self.draft.params, st, dst, drafted,
            qs, sp, rng, gamma=gamma)


class Overloaded(RuntimeError):
    """Admission queue is full — callers should shed load (HTTP 429)."""


class MigratedAway(RuntimeError):
    """The request's state was exported to a peer replica (instant
    drain). Not a failure: the router resumes the generation on the
    peer from the migrated KV, and clients never see this exception —
    the server maps it to a retryable error the router absorbs."""

    def __init__(self, request_id: str = ""):
        super().__init__(
            f"request {request_id or '<unknown>'} migrated to a peer "
            "replica")
        self.request_id = request_id


class _Slot:
    """Host-side record for one admitted request."""

    __slots__ = ("fut", "out", "lps", "max_new", "queue", "stop",
                 "kv_toks", "owned", "node_refs", "freed",
                 "meta", "sampling", "aid", "block_charge",
                 "prefilling")

    def __init__(self, fut, max_new: int, queue, stop=()):
        self.fut = fut
        self.out: list[int] = []
        self.lps: list[float] = []  # chosen-token logprobs, out-aligned
        self.max_new = max_new
        self.queue = queue  # per-request token stream (None for oneshot)
        self.stop = stop    # token-id sequences that end generation
        # tenancy/preemption bookkeeping: the scheduling record, plus
        # enough of the original request (sampling knobs, adapter id)
        # to re-enqueue it if this decode gets preempted
        self.meta: ReqMeta | None = None
        self.sampling: dict | None = None
        self.aid = 0
        self.block_charge = 0  # pool blocks charged to the tenant ledger
        # paged-KV bookkeeping: the tokens whose KV this slot's blocks
        # hold (full prompt incl. any registered prefix, then every
        # emitted token UNTRIMMED — stop-sequence trimming edits `out`,
        # not the cache), the exclusively-owned physical blocks by
        # logical block index, and the radix nodes this request holds
        # refs on (shared prefix chain + in-flight-indexed own blocks).
        self.kv_toks: list[int] = []
        self.owned: dict[int, int] = {}
        self.node_refs: list = []
        self.freed = False  # block bookkeeping already released
        # chunked prefill: {"suffix": [...], "fed": n} while the prompt
        # is still being fed in budget slices; None once decodable.
        # Mid-prefill the slot's device row is FROZEN and the record is
        # excluded from decode snapshots, preemption, and KV export.
        self.prefilling: dict | None = None


class ContinuousBatcher:
    """Host orchestrator: admission, per-request budgets, EOS
    retirement. API-compatible with server.Batcher (`submit`, `close`,
    `.calls`/`.requests` counters), so `create_serving_app` can swap it
    in without touching the handler.

    `.calls` counts decode steps and `.requests` admitted requests —
    `requests / calls` is NOT a mean batch here; the continuous
    analog `tokens_emitted / calls` (mean occupied slots per step) is
    exported as `.occupancy()`.
    """

    @startup_span("startup.batcher")
    def __init__(self, engine: InferenceEngine, gpu_lock: asyncio.Lock,
                 *, max_slots: int = 8, chunk: int = 4,
                 prefill_chunk_tokens: int = PREFILL_CHUNK_TOKENS,
                 prefixes: dict[str, list[int]] | None = None,
                 max_pending: int = 256,
                 pipeline_depth: int | None = None,
                 window_ms: float = 0.0,
                 kv_block_size: int = 64,
                 kv_pool_blocks: int | None = None,
                 paged_attention_impl: str = "auto",
                 draft: InferenceEngine | None = None,
                 spec_gamma: int = 4,
                 kv_spill_bytes: int | None = None,
                 tenancy=None, clock=None):
        # window_ms accepted (and ignored) for constructor parity with
        # Batcher: admission is per-token here, there is no window.
        del window_ms
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        # Chunked prefill (ISSUE 9): instead of prefilling a whole
        # prompt in one dispatch while every active decode stalls, the
        # worker feeds at most `prefill_chunk_tokens` prompt tokens per
        # loop iteration through the fused paged append path,
        # interleaved with decode chunks — the per-step token budget
        # that keeps the decode batch dense. One `[1, budget]` program
        # serves every prompt length (clamped below to the cache
        # width: no prompt is longer).
        if prefill_chunk_tokens < 1:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 1, got "
                f"{prefill_chunk_tokens}")
        # Speculative decoding on paged KV (ISSUE 9): with a draft
        # engine, every decode iteration becomes a draft(gamma) +
        # verify(gamma+1) round batched across slots; accepted tokens
        # append through the block tables, rejections roll the cursors
        # back. Replaces chunk-scan decode (spec rounds are the chunk).
        if spec_gamma < 1:
            raise ValueError(f"spec_gamma must be >= 1, got {spec_gamma}")
        self.spec_gamma = spec_gamma
        # Dispatch-ahead depth: with depth 2 the worker queues the next
        # decode chunk while the previous one is still computing, so
        # host-side emit/retirement work overlaps device time instead
        # of idling the chip between chunks. The price is bounded
        # speculation: a slot that retires early (EOS/stop) may decode
        # up to (depth-1) x chunk garbage tokens before the host sees
        # it — the free-row cost model this engine is built on. Depth 1
        # restores strict per-chunk retirement.
        #
        # Default is backend-aware (measured, docs/perf-notes.md): on
        # an accelerator the overlap hides host time behind device
        # time; on CPU "device" compute shares the host's cores, so
        # speculation only adds waste (-6% on the loadtest A/B).
        if pipeline_depth is None:
            pipeline_depth = 2 if jax.default_backend() == "tpu" else 1
        if pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}")
        self.pipeline_depth = pipeline_depth
        # The worker decodes up to `chunk` tokens per dispatch (one
        # scanned program) — per-token host dispatch is the continuous
        # design's overhead tax. Admission happens between dispatches:
        # a queued request waits at most chunk-1 tokens at depth 1, up
        # to ~pipeline_depth x chunk under dispatch-ahead (a freed
        # slot is only observed once its chunk drains) — still far
        # under a window group's full-generation wait. Compiles stay
        # bounded: one program per steps value in [1, chunk].
        self.chunk = chunk
        self.cengine = ContinuousEngine(
            engine, max_slots,
            block_size=kv_block_size, num_blocks=kv_pool_blocks,
            paged_attention_impl=paged_attention_impl, draft=draft)
        self.prefill_chunk_tokens = min(prefill_chunk_tokens,
                                        self.cengine.kv_width)
        # Automatic radix prefix cache over the block pool: every
        # admitted prompt's full blocks are indexed by token prefix
        # (at admission, so even in-flight prefills are sharable), and
        # retirement donates a request's blocks back to the tree. A new
        # prompt sharing a cached prefix seeds its prefill from those
        # blocks and only computes the suffix. Refcount-0 blocks are
        # LRU-evicted when admission needs the space. Registered
        # `prefixes` are names for token lists and ride the same
        # cache: the first use computes the prefix, later uses hit.
        self._radix = RadixPrefixCache(self.cengine.pool)
        # A block of KV cells cannot seed a slot whose recurrent layers
        # have no state at that boundary (state snapshots are not kept):
        # for such a model admission matches nothing and no block is
        # indexed, so every prompt, a preempted request's replay too,
        # is computed from its first token.
        self._radix_on = not self.cengine.recurrent
        self.state_resets = 0     # slots adopted with a zeroed state
        # Block lifecycle ledger (ISSUE 13): attached to the pool
        # before any alloc, so every block birth/death is booked to a
        # cause and births − frees reconciles against pool.in_use (the
        # eviction-forensics conservation invariant). The server binds
        # its on_* hooks to /metrics families; /debug/profile and
        # bench read snapshot() via cache_anatomy().
        self.cache_ledger = CacheLedger()
        self.cengine.pool.attach_ledger(self.cache_ledger)
        # Host-RAM spill tier (ISSUE 19): with a byte budget, radix
        # eviction demotes block contents to host numpy instead of
        # discarding (deaths booked `spill`), and admission planning
        # promotes them back with a host->device copy when the same
        # prefix returns (`note_restore`). Conservation extends to
        # content: (births - restores) - (non-spill deaths + drops)
        # == live + spilled. None disables the tier entirely.
        if kv_spill_bytes is not None and kv_spill_bytes < 0:
            raise ValueError(
                f"kv_spill_bytes must be >= 0, got {kv_spill_bytes}")
        self._spill_tier: HostSpillTier | None = None
        if kv_spill_bytes is not None:
            self.cengine.refuse_recurrent(
                "kv_spill_bytes (a spilled block restores KV cells, not "
                "the state at its boundary)")
            self._spill_tier = HostSpillTier(
                kv_spill_bytes, self.cengine.kv_block_bytes())
            self._radix.attach_spill(self._spill_tier,
                                     self._spill_reader)
        self._dirty: list[int] = []  # freed slots awaiting table reset
        self.prefix_hits = 0      # admissions that reused cached cells
        self.prefix_misses = 0
        self.tokens_prefilled = 0  # suffix tokens actually computed
        self.tokens_reused = 0     # prompt cells served from cache
        # optional hook(computed: int, reused: int, hit: bool,
        # tenant: str), called per admission — the server wires metrics
        # (including the tenant-labelled hit/miss series) through this
        self.on_prefix = None
        # Per-request token timelines (obs.timeline): every request
        # gets a RequestTimeline stamped with its structural events
        # plus every emitted token's timestamp; the bounded store backs
        # `/v1/requests/{id}/timeline`. The injectable clock lets tests
        # assert exact ITL math. Like on_prefix, the optional hooks —
        # on_itl(gap_s) per decode token, on_queue_wait(wait_s) per
        # first admission — feed server histograms and must never kill
        # the worker.
        self._clock = clock or time.monotonic
        self.timelines = TimelineStore()
        self.on_itl = None
        self.on_queue_wait = None
        # on_spec_round(proposed: int, accepted: int) — per speculative
        # verify round; the server feeds the spec-acceptance SLO
        self.on_spec_round = None
        # Runtime kill switch for speculative decoding (the fleet
        # controller's disable_draft actuator flips it via POST
        # /v1/spec). Off: spec rounds and draft-cache seeding stop,
        # plain decode continues; the draft engine and its caches stay
        # allocated. Re-enabling mid-flight is safe only at low load —
        # slots admitted while disabled have no draft KV row, so spec
        # rounds would verify against a stale draft cache; prefer to
        # re-enable when the batcher drains.
        self.spec_enabled = True
        # optional obs.Tracer: when set (the server wires it), every
        # decode-chunk dispatch opens a `decode.attention` span in the
        # executor thread, tagged with the RESOLVED attention impl —
        # traces show which kernel served a step
        self.tracer = None
        # Step-anatomy profiler (ISSUE 8): always on — pure-python
        # phase accounting is a few clock reads per iteration. The
        # server binds `/metrics` histograms through profiler.on_phase
        # (the on_prefix hook idiom) and `/debug/profile` reads
        # profiler.snapshot(); bench --attribution reads it directly.
        # Shares the injectable clock so tests reconcile profiler
        # totals against timeline stamps on one timebase. The phases
        # are also spans of the JAX profiler's trace (`sched.<phase>`
        # under `sched.iteration`), which record nothing unless a
        # profiler session is open (docs/observability.md).
        self.profiler = PhaseProfiler(
            clock=self._clock, annotate=jax.profiler.TraceAnnotation)
        # Compile-watch: a view of the dispatch caches of the jitted
        # callables on this batcher's hot path, which are called as
        # they are (no wrapper, nothing per dispatch). An entry past a
        # function's first is a retrace — counted when asked, surfaced
        # as serving_recompiles_total{fn} once the server binds
        # compile_watch.on_recompile. (warmup() walks the bounded
        # compile set through these functions, so the counters start
        # at the warmed-shape count; steady state is flat — the alert
        # is on the RATE.)
        self.compile_watch = CompileWatch()
        ce = self.cengine
        self.compile_watch.watch(ce._step_jit, "decode_step")
        self.compile_watch.watch(ce._reset_jit, "reset_slots")
        self.compile_watch.watch(ce._append_jit, "prefill_append")
        if ce.draft is not None:
            self.compile_watch.watch(ce._spec_draft_jit, "spec_draft")
            self.compile_watch.watch(ce._spec_verify_jit, "spec_verify")
        # Shared prefixes (system prompts): token lists registered at
        # construction, prepended to a request that names one.
        self._prefixes = dict(prefixes or {})
        for pname, ptoks in self._prefixes.items():
            if not ptoks or len(ptoks) >= engine.ec.max_len:
                raise ValueError(
                    f"prefix {pname!r}: length {len(ptoks)} invalid "
                    f"for max_len {engine.ec.max_len}")
        self.engine = engine
        self.gpu_lock = gpu_lock
        self.calls = 0            # decode steps (device invocations)
        self.requests = 0         # admitted requests
        self.tokens_emitted = 0
        # Multi-tenant QoS (kubeflow_tpu.tenancy): with a TenancyConfig
        # the FIFO pending deque becomes a priority + weighted
        # fair-share queue and a per-tenant ledger enforces rate limits
        # and KV shares; interactive arrivals may PREEMPT the youngest
        # batch-class decode (see _maybe_preempt). Tenant-blind
        # deployments (tenancy=None) keep the exact FIFO deque.
        self.tenancy = tenancy
        self._ledger = (TenantLedger(tenancy)
                        if tenancy is not None else None)
        if tenancy is not None:
            self._pending: Any = FairShareQueue(tenancy, self._ledger)
        else:
            self._pending = collections.deque()
        self.preemptions = 0      # batch decodes evicted for interactive
        self._interactive_blocked = False  # interactive plan deferred
        self._seq = 0             # admission sequence (preempt youngest)
        # EWMA of enqueue->finish service time, feeding the dynamic
        # Retry-After on Overloaded 429s
        self.service_ewma = 0.0
        # Backpressure: an unbounded admission queue turns overload
        # into unbounded client latency AND unbounded host memory;
        # past this depth _enqueue raises Overloaded (HTTP 429).
        self.max_pending = max_pending
        self._wake = asyncio.Event()
        self._active: dict[int, _Slot] = {}
        self._free = list(range(max_slots))
        self._st: SlotState | None = None
        # chunked-prefill progress queue (slot ids, FIFO: the oldest
        # admission finishes first, minimizing its TTFT) and the draft
        # model's per-slot cache (lazily built, like _st)
        self._prefill_q: collections.deque[int] = collections.deque()
        self._dst = None
        self.spec_proposed = 0  # drafted tokens proposed across rounds
        self.spec_accepted = 0  # drafted tokens accepted by the target
        # greedy filler knobs on free slots: a sampled leftover would
        # drag an all-greedy step into the sampled branch's argsorts
        self._temp = np.zeros(max_slots, np.float32)
        self._topk = np.zeros(max_slots, np.int32)
        self._topp = np.ones(max_slots, np.float32)
        # SamplingParams rebuild (3 host->device transfers) only when a
        # knob actually changed — at steady occupancy every decode
        # chunk reuses the cached device arrays.
        self._sp_cache: SamplingParams | None = None
        self._sp_dirty = True
        self._rng = jax.random.key(
            int.from_bytes(os.urandom(8), "little") >> 1)
        self._worker: asyncio.Task | None = None
        self._closed = False
        self._draining = False
        # migration halt: export_sequences() asks the worker to park
        # at its next loop boundary (never mid-admission — a cancel
        # there would strand requests in the worker's local buffers)
        self._halt = False
        # Admitted-but-unfinished request count. NOT derivable from
        # _pending/_active: the worker holds requests in local buffers
        # between popleft and slot assignment (prefill pipelining), so
        # drain() polling those containers would declare victory with a
        # request mid-prefill. Every record's fut resolves terminally
        # on every path (emit, error, cancel, close), so a done
        # callback is the one watertight decrement point.
        self._admitted = 0

    def occupancy(self) -> float:
        return self.tokens_emitted / self.calls if self.calls else 0.0

    def kv_blocks_in_use(self) -> int:
        """Pool blocks held by active requests + the radix cache (the
        `serving_kv_blocks_in_use` gauge; x `kv_block_bytes()` for
        HBM)."""
        return self.cengine.pool.in_use

    def ssm_state_bytes(self) -> int:
        """Recurrent-state bytes of the slots that hold a request (the
        `serving_ssm_state_bytes` gauge); 0 for a model without
        recurrent layers."""
        return len(self._active) * self.cengine.state_bytes_per_slot()

    def prefix_cache_stats(self) -> dict:
        return {
            "hits": self.prefix_hits,
            "misses": self.prefix_misses,
            "tokens_prefilled": self.tokens_prefilled,
            "tokens_reused": self.tokens_reused,
            "cached_blocks": self._radix.cached_blocks,
            "blocks_in_use": self.cengine.pool.in_use,
            # host spill tier occupancy (0s when the tier is off)
            "spilled_blocks": (self._spill_tier.spilled_blocks
                               if self._spill_tier is not None else 0),
            "spilled_bytes": (self._spill_tier.spilled_bytes
                              if self._spill_tier is not None else 0),
            # top-K decayed prefix heat, 16-hex hashed names — the
            # per-replica half of the fleet heat map (`/fleet/cache`)
            "heat": self._radix.heat_digest(16),
        }

    def cache_anatomy(self) -> dict:
        """Cache-observatory snapshot for `/debug/profile` and bench:
        the lifecycle ledger (eviction causes, reuse-distance/age
        quantiles, defer causes, conservation fields) plus the prefix
        heat digest."""
        return {
            "ledger": self.cache_ledger.snapshot(),
            "heat": self._radix.heat_digest(16),
        }

    @startup_span("startup.warmup")
    def warmup(self) -> int:
        """Blocking ahead-of-traffic compile of every program admission
        and decode run (call before serving traffic; the app's
        on_startup hook does when create_serving_app(warmup=True)):
        `adopt_slot`, `copy_cells`, `append_rows` at the one slice
        shape, `step` for 1 .. `chunk` steps and `reset_slots` at each
        power-of-two list size — on a scratch state, through the same
        host entries and watches the worker uses, so that traffic
        meets no new signature. Returns the number of programs
        warmed."""
        ce = self.cengine
        st = ce.init_slots()
        sp, rng = self._sp(), self._rng
        toks = np.zeros((1, self.prefill_chunk_tokens), np.int32)
        table = np.zeros(ce.blocks_per_slot, np.int32)
        # twice: the first admission meets a state and a key fresh
        # from the host, every later one the previous program's
        # outputs — two signatures to a jit's cache, one program
        for _ in range(2):
            st = ce.adopt_slot(st, 0, table, 0, 0)
            st = ce.copy_cells(st, 0, 0, 1)
            st, _, _, rng = ce.append_rows(st, [0], toks, [1], [True],
                                           sp, rng)
        for steps in range(1, self.chunk + 1):
            st, _, _, rng = ce.step(st, sp, rng, steps)
        sizes = [1 << i for i in range(pow2_ceil(ce.S).bit_length())]
        for n in sizes:
            st = ce.reset_slots(st, [0] * n)
        jax.block_until_ready(st)
        return 3 + self.chunk + len(sizes)

    # -- public API -------------------------------------------------------

    async def submit(self, tokens: list[int], max_new: int,
                     sampling: tuple, *, with_logprobs: bool = False):
        """Generate `max_new` tokens for one prompt; resolves when THIS
        request finishes (other slots keep decoding). The result is
        EOS-padded to exactly max_new — interchangeable with the window
        Batcher's fixed-shape contract (a request that hits EOS early
        stops COMPUTING early here; the pad is host-side) — with or
        without logprobs, so the response SHAPE never depends on the
        server's batcher mode. Requests with stop sequences return the
        TRIMMED output unpadded — stopping short is the ask.
        with_logprobs=True returns (tokens, logprobs); logprobs stays
        unpadded (entries exist only for computed tokens, through the
        first EOS)."""
        fut = self._enqueue(tokens, max_new, sampling, queue=None)
        out, lps = await fut
        eos = self.engine.ec.eos_token
        if eos is not None and len(out) < max_new \
                and not dict(sampling).get("stop"):
            out = out + [eos] * (max_new - len(out))
        return (out, lps) if with_logprobs else out

    def open_stream(self, tokens: list[int], max_new: int,
                    sampling: tuple):
        """Enqueue a streaming request NOW (admission errors — incl.
        Overloaded — raise here, synchronously) and return (fut,
        queue). The server calls this BEFORE sending SSE headers so
        overload is a clean 429, never a mid-stream abort."""
        q: asyncio.Queue = asyncio.Queue()
        return self._enqueue(tokens, max_new, sampling, queue=q), q

    async def stream(self, tokens: list[int], max_new: int,
                     sampling: tuple):
        """Async-iterate tokens as they decode (SSE feed). The stream
        ends at EOS or max_new; the caller owns trimming/decoding."""
        fut, q = self.open_stream(tokens, max_new, sampling)
        try:
            while True:
                item = await q.get()
                if item is None:
                    break
                yield item
            await fut  # surface admission/step errors after drain
        finally:
            # a consumer that stops iterating (client disconnect mid-
            # SSE) must release its slot — otherwise it decodes to
            # max_new into a dead queue and reconnect-loop clients
            # could pin every slot
            if not fut.done():
                fut.cancel()

    def _enqueue(self, tokens, max_new, sampling, *, queue):
        if self._closed:
            raise RuntimeError("batcher is shut down")
        if self._draining:
            raise RuntimeError("batcher is draining")
        if len(self._pending) >= self.max_pending:
            raise Overloaded(
                f"{len(self._pending)} requests already queued "
                f"(max_pending={self.max_pending})")
        cap = self.engine.ec.max_len
        if len(tokens) + max_new > cap:
            raise ValueError(
                f"prompt {len(tokens)} + max_new {max_new} exceeds "
                f"model max_len {cap}")
        sampling = dict(sampling)
        # the tenant identity rides the sampling channel (like adapter
        # and prefix do) but is popped back out — it is routing
        # metadata, not a sampling knob
        tenant = sampling.pop("tenant", "")
        # the request id rides the sampling channel the same way; the
        # server mints it (X-Request-Id) — direct batcher callers get a
        # sequence-derived fallback so timelines always have a key
        request_id = str(sampling.pop("request_id", "")) \
            or f"req-{self._seq:06d}"
        spec = (self.tenancy.resolve(tenant)
                if self.tenancy is not None else None)
        if self._ledger is not None:
            # rate-limit door: raises tenancy.Throttled (HTTP 429 with
            # the bucket's refill time) before anything is spent
            self._ledger.check_request(spec.name)
        # multi-LoRA: the adapter name rides the sampling channel;
        # resolve (and reject unknowns) HERE, before a slot is spent
        adapter = sampling.get("adapter", "")
        pack = self.engine.adapter_pack
        if adapter and pack is None:
            raise ValueError(
                f"adapter {adapter!r} requested but no adapter pack "
                "is loaded on this engine")
        aid = pack.resolve(adapter) if pack else 0
        # a registered prefix is a name for tokens: expanded HERE, so
        # the request plans, replays and migrates as its whole prompt
        prefix = sampling.pop("prefix", "")
        if prefix:
            if prefix not in self._prefixes:
                raise ValueError(
                    f"unknown prefix {prefix!r}; registered: "
                    f"{sorted(self._prefixes)}")
            if adapter:
                # prefix KV is computed with the BASE weights; reusing
                # it under an adapter would silently serve a hybrid
                raise ValueError(
                    "prefix does not compose with adapter (the shared "
                    "KV is base-model KV)")
            plen = len(self._prefixes[prefix])
            if plen + len(tokens) + max_new > cap:
                raise ValueError(
                    f"prefix {plen} + prompt {len(tokens)} + max_new "
                    f"{max_new} exceeds model max_len {cap}")
            tokens = list(self._prefixes[prefix]) + list(tokens)
        if self._worker is None or self._worker.done():
            self._worker = asyncio.get_event_loop().create_task(
                self._run())
        fut: asyncio.Future = asyncio.get_event_loop().create_future()
        self._admitted += 1
        fut.add_done_callback(lambda _f: self._req_done())
        tl = RequestTimeline(
            request_id,
            tenant=spec.name if spec is not None else tenant,
            prompt_tokens=len(tokens), max_new=max_new,
            clock=self._clock)
        meta = ReqMeta(
            tenant=spec.name if spec is not None else "",
            priority=spec.priority if spec is not None else "standard",
            weight=spec.weight if spec is not None else 1.0,
            cost=float(max_new),
            t_enqueue=self._clock(),
            seq=self._seq,
            ns=(spec.name if spec is not None and spec.prefix_isolation
                else ""),
            request_id=request_id, timeline=tl)
        self._seq += 1
        tl.event("enqueue", tokens=len(tokens), max_new=max_new,
                 priority=meta.priority)
        self.timelines.add(tl)
        self._pending.append(
            (tokens, max_new, sampling, fut, queue, aid, meta))
        self._wake.set()
        return fut

    def _req_done(self) -> None:
        self._admitted -= 1

    # -- worker -----------------------------------------------------------

    def _sp(self) -> SamplingParams:
        if self._sp_dirty or self._sp_cache is None:
            self._sp_cache = SamplingParams(
                temperature=jnp.asarray(self._temp),
                top_k=jnp.asarray(self._topk),
                top_p=jnp.asarray(self._topp))
            self._sp_dirty = False
        return self._sp_cache

    def _release(self, slot: int, *, cause: str = "refdrop") -> None:
        """Return a slot to the pool with greedy filler knobs (a
        leftover sampled temperature would drag all-greedy steps into
        the sampled branch's full-vocab argsorts). Releases the slot's
        KV blocks (deaths booked to `cause` — refdrop for ordinary
        retirement, pressure for preemption, migration for export) and
        marks its device-side block table dirty (reset to trash before
        the next admission, so the freed blocks stop receiving the
        retired slot's garbage decode writes)."""
        rec = self._active.pop(slot, None)
        self._free.append(slot)
        self._temp[slot], self._topk[slot], self._topp[slot] = 0, 0, 1.0
        self._sp_dirty = True
        if rec is not None:
            self._release_blocks(rec, cause=cause)
            self._dirty.append(slot)

    def _release_blocks(self, rec: _Slot, *,
                        cause: str = "refdrop") -> None:
        """Drop a request's claim on pool blocks: unref its radix
        nodes (tree-owned blocks stay cached, evictable once idle) and
        free the exclusively-owned ones. Idempotent."""
        if rec.freed:
            return
        rec.freed = True
        if self._ledger is not None and rec.meta is not None:
            self._ledger.note_slot_released(rec.meta.tenant,
                                            rec.block_charge)
        if rec.node_refs:
            self._radix.unref(rec.node_refs)
            rec.node_refs = []
        if rec.owned:
            self.cengine.pool.free(rec.owned.values(), cause=cause)
            rec.owned = {}

    def _cache_blocks(self, rec: _Slot) -> None:
        """At clean retirement, donate the request's full KV blocks to
        the radix tree instead of freeing them — the automatic prefix
        cache. Only cells [0, len(kv_toks) - 1) are guaranteed written
        (the final token's KV may still be in flight), so only full
        blocks below that line are indexed; in-flight garbage writes
        land strictly above it (the slot's cursor never moves back),
        so adopted blocks are immutable. Must run BEFORE
        `_release_blocks` frees the rest."""
        if (rec.freed or not rec.kv_toks or rec.prefilling is not None
                or not self._radix_on):
            # mid-chunked-prefill retirement (cancel): cells past the
            # fed frontier are unwritten — nothing safely cacheable
            return
        bs = self.cengine.block_size
        n_full = (len(rec.kv_toks) - 1) // bs
        if n_full <= 0:
            return
        blocks = {i: rec.owned[i] for i in range(n_full)
                  if i in rec.owned}
        adopted, _ = self._radix.insert(
            rec.kv_toks[:n_full * bs], blocks,
            ns=rec.meta.ns if rec.meta is not None else "")
        for i in adopted:
            del rec.owned[i]
        # Blocks we OFFERED but the tree declined already have an edge
        # for the same token path (a concurrent twin prefill won the
        # insert): this copy's content is a duplicate — book its death
        # as `divergence`, distinct from the slot's ordinary refdrop
        # tail (the final partial block et al, freed by _release).
        dup = [blocks[i] for i in blocks if i not in adopted]
        if dup:
            for i in list(rec.owned):
                if rec.owned[i] in dup:
                    del rec.owned[i]
            self.cengine.pool.free(dup, cause="divergence")

    def _index_inflight(self, rec: _Slot) -> None:
        """Once the prompt is fed, index its full blocks in the radix
        tree immediately — a concurrent request sharing the prefix can
        seed from them while this one is still decoding (device order
        is safe: its reads are dispatched after our writes). Created
        nodes start with a ref held by this request (`hold=True`): the
        tree must not evict a block our own table points at."""
        bs = self.cengine.block_size
        n_full = len(rec.kv_toks) // bs
        if n_full <= 0 or not self._radix_on:
            return
        blocks = {i: rec.owned[i] for i in range(n_full)
                  if i in rec.owned}
        adopted, held = self._radix.insert(
            rec.kv_toks[:n_full * bs], blocks, hold=True,
            ns=rec.meta.ns if rec.meta is not None else "")
        for i in adopted:
            del rec.owned[i]
        rec.node_refs.extend(held)

    def _finish(self, slot: int, rec: _Slot) -> None:
        self._cache_blocks(rec)
        self._release(slot)
        if rec.meta is not None:
            dt = self._clock() - rec.meta.t_enqueue
            self.service_ewma = (0.8 * self.service_ewma + 0.2 * dt
                                 if self.service_ewma > 0 else dt)
            if self._ledger is not None:
                self._ledger.note_completed(rec.meta.tenant)
            if rec.meta.timeline is not None:
                rec.meta.timeline.event("finish", tokens=len(rec.out))
        if rec.queue is not None and not rec.fut.done():
            rec.queue.put_nowait(None)
        if not rec.fut.done():
            rec.fut.set_result((rec.out[:rec.max_new],
                                rec.lps[:rec.max_new]))

    def _emit(self, slot: int, rec: _Slot, token: int, lp: float, *,
              decode: bool = True) -> None:
        rec.out.append(token)
        rec.lps.append(lp)
        rec.kv_toks.append(token)  # cache-content log, never trimmed
        if rec.meta is not None and rec.meta.timeline is not None:
            first = not rec.meta.timeline.tokens
            gap = rec.meta.timeline.token()
            if first:
                self._trace_first_token(rec.meta.timeline)
            # first token (and first after a preempt/resume hole)
            # returns None: not an inter-token latency
            if decode and gap is not None and self.on_itl is not None:
                try:
                    self.on_itl(gap)
                except Exception:  # noqa: BLE001 — metrics hook
                    pass           # must never kill the worker
        if self._ledger is not None and rec.meta is not None:
            # tokens/s pacing: generated tokens charge the bucket; a
            # tenant in debt stops being popped until it refills
            self._ledger.charge_tokens(rec.meta.tenant, 1)
        if decode:
            # admission-time first tokens (prefill) stay out of the
            # occupancy numerator — calls counts decode steps only
            self.tokens_emitted += 1
        if rec.queue is not None and not rec.fut.done():
            rec.queue.put_nowait(token)
        # stop sequences: the moment a sequence completes as the
        # output's suffix, trim it off (OpenAI semantics) and retire
        # the slot — the compute win the window batcher can't have
        # (its group runs to the group max regardless)
        for seq in rec.stop:
            n = len(seq)
            if n and len(rec.out) >= n and rec.out[-n:] == list(seq):
                rec.out = rec.out[:-n]
                rec.lps = rec.lps[:-n]
                self._finish(slot, rec)
                return
        eos = self.engine.ec.eos_token
        if len(rec.out) >= rec.max_new or (eos is not None
                                           and token == eos):
            self._finish(slot, rec)

    @staticmethod
    def _trace_first_token(tl: RequestTimeline) -> None:
        """Where a request's waits end: one instantaneous span in the
        profiler's trace, with the three parts of its time to the
        first token as the timeline holds them."""
        def us(seconds):
            return round(1e6 * (seconds or 0.0), 1)

        with jax.profiler.TraceAnnotation(
                "sched.first_token", request=tl.request_id,
                prompt_tokens=tl.prompt_tokens,
                reused_tokens=tl.prefill_reused,
                slices=tl.prefill_slices,
                queue_wait_us=us(tl.queue_wait_s),
                prefill_us=us(tl.prefill_s),
                prefill_wait_us=us(tl.prefill_wait_s)):
            pass

    @staticmethod
    def _fail(fut, queue, exc) -> None:
        if queue is not None and not fut.done():
            queue.put_nowait(None)  # unblock a stream() consumer
        if not fut.done():
            fut.set_exception(exc)

    def _fail_all(self, exc) -> None:
        """Slot state is unrecoverable (donated buffers consumed by a
        failed dispatch): fail every active request deterministically
        and drop the state so the next admission re-inits."""
        for slot, rec in list(self._active.items()):
            self._release(slot)
            self._fail(rec.fut, rec.queue, exc)
        self._st = None
        self._dst = None
        self._prefill_q.clear()
        # the pool array just died with the state: cached tree blocks
        # describe content that no longer exists — drop them, and the
        # pending table resets with them (nothing left to reset)
        self._radix.clear()
        self._dirty.clear()

    def _maybe_preempt(self) -> None:
        """When an interactive request is waiting and can't admit —
        every slot is busy, or its block plan just deferred — evict the
        YOUNGEST batch-class decode. Its full KV blocks are donated to
        the radix tree first, so re-admission replays the prefix from
        cache and only recomputes the partial tail: the cheap
        preemption the paged/radix layer was built to enable. One
        victim per worker iteration keeps it bounded; the next
        iteration preempts again if the pressure persists."""
        if self._ledger is None:
            return
        blocked = self._interactive_blocked
        self._interactive_blocked = False
        if self._free and not blocked:
            return
        if not self._pending.has_waiting("interactive"):
            return
        victim, vseq = None, -1
        for slot, rec in self._active.items():
            m = rec.meta
            if m is None or m.priority != "batch" or rec.fut.done():
                continue
            if rec.prefilling is not None:
                # mid-chunked-prefill: its blocks hold no complete KV
                # to cache and its replay would cost a full re-prefill
                # for zero decode progress reclaimed — never a victim
                continue
            if m.seq > vseq:
                victim, vseq = slot, m.seq
        if victim is not None:
            self._preempt(victim)

    def _preempt(self, slot: int) -> None:
        with self.profiler.phase("preempt"):
            self._preempt_inner(slot)

    def _preempt_inner(self, slot: int) -> None:
        """Evict one active decode and re-enqueue it at the head of
        its tenant's queue. The clean-retirement path minus resolving
        the future: cache the full blocks, release the slot (its table
        resets to trash before the next admission reuses the freed
        blocks — same invariant as normal retirement, which is why the
        worker preempts BEFORE the dirty-slot reset step). Replay is
        token-identical under greedy decoding: the resumed prompt is
        prompt + everything emitted so far, its prefix KV comes back
        bit-exact from the cache, and the recomputed suffix produces
        the same argmax continuation."""
        rec = self._active[slot]
        meta = rec.meta
        self._cache_blocks(rec)
        self._release(slot, cause="pressure")
        self.preemptions += 1
        if self._ledger is not None:
            self._ledger.note_preempted(meta.tenant)
        if meta.timeline is not None:
            meta.timeline.event("preempt", slot=slot,
                                emitted=len(rec.out))
        meta.resume = {"out": list(rec.out), "lps": list(rec.lps),
                       "max_new": rec.max_new}
        # the re-enqueued item plans blocks with the REMAINING budget
        # (full already holds the emitted tokens) and its fair-share
        # cost drops to the remainder so the tenant isn't double-billed
        remaining = max(1, rec.max_new - len(rec.out))
        meta.cost = float(remaining)
        self._pending.appendleft(
            (list(rec.kv_toks), remaining, rec.sampling, rec.fut,
             rec.queue, rec.aid, meta))
        self._wake.set()

    def tenant_stats(self) -> dict:
        """Per-tenant live usage + queue depth ({} when tenant-blind)
        — the `serving_tenant_*` collector and `/v1/models` read this."""
        if self._ledger is None:
            return {}
        stats = self._ledger.stats()
        for tenant, depth in self._pending.depths().items():
            stats.setdefault(tenant, {})["queued"] = depth
        return stats

    def _spill_reader(self, block: int):
        """Device->host snapshot of one pool block's K/V payload —
        the reader `RadixPrefixCache.evict` demotes through. Returns
        `(k, v)` numpy `[L, 1, bs, n_kv, hd]`, or None when there is
        no device state yet. Runs synchronously on the caller's
        thread; a concurrently-donated state raises (deleted buffer),
        which the cache treats as "demote failed, discard instead"."""
        if self._st is None:
            return None
        return self.cengine.export_blocks(self._st, [block])

    async def _restore_spilled(self, item) -> None:
        """Promote this request's spilled full-block prefix back into
        the pool BEFORE block planning, so `_plan_blocks` radix-hits
        it exactly as if the blocks had never been evicted. Restores
        are token-identical by the canonical-form invariant: the tier
        key is the full token prefix, and the payload re-enters the
        pool through the same `import_blocks` scatter migration uses.
        Best-effort throughout — any failure (pool full, donated
        state, partial insert) degrades to plain prefill of the
        missing cells and never raises into admission. Books
        `note_restore` for adopted blocks and stamps `meta.restored`
        so the admission's `on_prefix` can split the metric source."""
        tier = self._spill_tier
        if tier is None or tier.spilled_blocks == 0:
            return
        tokens, meta = item[0], item[6]
        full = [int(t) for t in tokens]
        ns = meta.ns
        bs = self.cengine.block_size
        nodes, _pnode, _plen = self._radix.match(full, ns=ns)
        # walk the tier forward from the cached frontier; the planner
        # always leaves >= 1 token to prefill, so a block whose last
        # cell is the final prompt token is useless — stop before it
        i = len(nodes) * bs
        end = i
        while (end + bs <= len(full) - 1
               and tier.contains(ns, full[:end + bs])):
            end += bs
        n = (end - i) // bs
        if n <= 0:
            return
        pool = self.cengine.pool
        fresh = pool.alloc(n)
        if fresh is None:
            # evicting to restore can itself demote colder blocks —
            # the tier's LRU decides which contents deserve host RAM
            self._radix.evict(n - pool.num_free)
            fresh = pool.alloc(n)
            if fresh is None:
                return
        payloads = []
        for j in range(n):
            p = tier.pop(ns, full[:i + (j + 1) * bs])
            if p is None:
                # budget dropped it between probe and pop (a demote
                # during our own evict above) — restore what we have
                break
            payloads.append(p)
        if not payloads:
            pool.free(fresh, cause="refdrop")
            return
        if len(payloads) < n:
            pool.free(fresh[len(payloads):], cause="refdrop")
            fresh = fresh[:len(payloads)]
            n = len(payloads)
        k = np.concatenate([p[0] for p in payloads], axis=1)
        v = np.concatenate([p[1] for p in payloads], axis=1)
        loop = asyncio.get_event_loop()
        done = False
        booked = False
        try:
            if self._st is None:
                self._st = self.cengine.init_slots()

            def run_restore():
                # read self._st INSIDE the lock: import_blocks donates
                # the buffers (same discipline as import_sequence)
                return self.cengine.import_blocks(self._st, fresh, k, v)

            async with self.gpu_lock:
                self._st = await loop.run_in_executor(None, run_restore)
            # every popped payload left the tier and reached the
            # device: that IS the restore, whether or not the tree
            # adopts each block below (duplicates die as divergence)
            self.cache_ledger.note_restore(n)
            booked = True
            blocks = {len(nodes) + j: b for j, b in enumerate(fresh)}
            adopted, _ = self._radix.insert(full[:end], blocks, ns=ns)
            dup = [b for j, b in blocks.items() if j not in adopted]
            done = True
        finally:
            if not done:
                # import failed: the blocks never became cached
                # content, and the popped payloads are gone — content
                # deaths unless the restore was already booked
                pool.free(fresh, cause="refdrop")
                if not booked:
                    self.cache_ledger.note_spill_drop(n)
                if self._st is not None and any(
                        leaf.is_deleted() for leaf in
                        jax.tree.leaves(self._st)
                        if hasattr(leaf, "is_deleted")):
                    self._fail_all(RuntimeError(
                        "slot state lost to donated spill restore"))
        if dup:
            # someone re-cached (part of) this prefix while we copied:
            # the tree kept its blocks, ours are duplicates
            pool.free(dup, cause="divergence")
        if n > len(dup):
            meta.restored += (n - len(dup)) * bs

    def _plan_blocks(self, item):
        """Match one request against the radix cache and reserve its
        physical blocks. Returns a plan dict, or None when the pool
        can't cover it even after evicting idle cached blocks — the
        caller defers the request until retirements free space.

        Plan fields: `full` (the prompt — the token stream the
        slot's KV will hold), `suffix` (what prefill
        must actually compute), `m` (cached cells seeding the prefill:
        cell index == token index by the blocks' canonical form),
        `chain` (ref'd radix nodes backing cells [0, m - m % bs)),
        `extra` (ref'd node whose block holds a PARTIAL tail of the
        match — read-only seed source; the diverging request writes
        its own fresh block, which is the copy-on-write), `fresh`
        (newly allocated blocks), `table` (the slot's physical block
        table, trash-padded)."""
        tokens, max_new, _sampling, _fut, _queue, _aid, meta = item
        ceng = self.cengine
        bs, mb = ceng.block_size, ceng.blocks_per_slot
        chain: list = []
        extra = None
        m = 0
        full = list(tokens)
        if self._st is not None and self._radix_on:
            nodes, pnode, plen = self._radix.match(full, ns=meta.ns)
            # always leave >= 1 token to prefill: sampling the
            # first output needs a forward pass over something
            m = min(len(nodes) * bs + plen, len(full) - 1)
            cut = m // bs
            if cut < len(nodes):
                # cap bit inside the full-block chain: the node at
                # the cut becomes the partial (copy-on-write) seed
                extra = nodes[cut] if m % bs else None
                nodes = nodes[:cut]
            elif m % bs:
                extra = pnode
            chain = nodes
        suffix = full[m:]
        n_total = -(-min(len(full) + max_new,
                         self.engine.ec.max_len) // bs)
        n_fresh = n_total - len(chain)
        if self._ledger is not None:
            # per-tenant KV share: a tenant already holding blocks may
            # not take the pool past its share — defer until its own
            # retirements free some. A tenant holding NOTHING always
            # admits (the share bounds CONCURRENT holdings; deferring a
            # lone oversized request forever would just wedge it).
            lim = self._ledger.block_limit(meta.tenant,
                                           ceng.pool.capacity)
            held = self._ledger.blocks_held(meta.tenant)
            if lim is not None and held > 0 and held + n_fresh > lim:
                self._ledger.note_throttled(meta.tenant, "kv_quota")
                self.cache_ledger.note_defer("kv_quota")
                return None
        fresh = ceng.pool.alloc(n_fresh)
        if fresh is None:
            self._radix.evict(n_fresh - ceng.pool.num_free)
            fresh = ceng.pool.alloc(n_fresh)
            if fresh is None:
                self.cache_ledger.note_defer("pool_exhausted")
                return None
        self._radix.ref(chain)
        if extra is not None:
            self._radix.ref([extra])
        # cache-ledger clock: one tick per admitted request; reused
        # chain/CoW blocks record their reuse distance in admissions
        self.cache_ledger.note_admission()
        reused = [n.block for n in chain]
        if extra is not None:
            reused.append(extra.block)
        if reused:
            self.cache_ledger.note_reuse(reused)
        table = np.zeros(mb, np.int32)
        phys = [n.block for n in chain] + fresh
        table[:len(phys)] = phys
        return {"full": full, "suffix": suffix, "m": m, "chain": chain,
                "extra": extra, "fresh": fresh, "table": table}

    def _drop_plan(self, plan) -> None:
        """Roll back `_plan_blocks` reservations (admission failed or
        the request was cancelled before its slot was adopted)."""
        self._radix.unref(plan["chain"])
        if plan["extra"] is not None:
            self._radix.unref([plan["extra"]])
        if plan["fresh"]:
            self.cengine.pool.free(plan["fresh"], cause="refdrop")

    async def _admit_group(self, items: list) -> None:
        """Admit up to len(self._free) requests: reserve each one's
        blocks (`_plan_blocks`: radix seeding, copy-on-write, tenancy
        quotas), point a FROZEN slot at them, and queue the suffix
        for budget-slice feeding by the worker loop. Admission is
        accounted in BLOCKS, not just slots: a request whose
        worst-case block need outruns the pool (even after evicting
        idle cached blocks) is deferred back to the queue head until
        retirements free blocks — later, smaller requests may admit
        past it. An adopt failure fails its own request (and every
        active request too when the donated buffers were consumed —
        see the except block)."""
        loop = asyncio.get_event_loop()
        deferred = []
        with self.profiler.phase("admit"):
            for item in items:
                if item[3].done():
                    continue
                try:
                    await self._restore_spilled(item)
                except Exception:  # noqa: BLE001 — best-effort
                    pass  # (plain prefill covers whatever's missing)
                plan = self._plan_blocks(item)
                if plan is None:
                    deferred.append(item)
                    if item[6].priority == "interactive":
                        # an interactive request couldn't get blocks:
                        # let the worker consider preempting a batch
                        # decode even though free SLOTS exist
                        self._interactive_blocked = True
                    continue
                try:
                    await self._adopt_one(loop, item, plan)
                except Exception as e:  # noqa: BLE001
                    self._drop_plan(plan)
                    self._fail(item[3], item[4], e)
                    # adopt donates self._st: a failure that fired
                    # AFTER dispatch leaves the old buffers consumed,
                    # and keeping them would crash the NEXT decode
                    # step with a confusing deleted-buffer error. A
                    # failure BEFORE dispatch (bad shapes, host-side
                    # raise) leaves them intact — then only this
                    # request dies. Distinguish the two instead of
                    # guessing.
                    if self._st is not None and any(
                            leaf.is_deleted() for leaf in
                            jax.tree.leaves(self._st)
                            if hasattr(leaf, "is_deleted")):
                        self._fail_all(RuntimeError(
                            f"slot state lost to donated adopt: {e}"))
            for item in reversed(deferred):
                self._pending.appendleft(item)

    async def _adopt_one(self, loop, item, plan) -> None:
        """Device + bookkeeping half of one chunked admission: install
        the planned block table on a free slot (frozen, cursor at the
        cached-seed length), copy the partial CoW seed block if any,
        and register the host record with its pending suffix."""
        tokens, max_new, sampling, fut, queue, aid, meta = item
        slot = self._free.pop()
        full, m = plan["full"], plan["m"]
        bs = self.cengine.block_size
        try:
            if self._st is None:
                self._st = self.cengine.init_slots()

            def run_adopt(st=self._st):
                st = self.cengine.adopt_slot(
                    st, slot, plan["table"], m, full[m], aid)
                if plan["extra"] is not None:
                    # cells [cut*bs, m) seed from the partially-matched
                    # shared block into this row's first fresh block —
                    # the copy half of copy-on-write
                    st = self.cengine.copy_cells(
                        st, plan["extra"].block, plan["fresh"][0],
                        m % bs)
                return st

            async with self.gpu_lock:
                self._st = await loop.run_in_executor(None, run_adopt)
        except Exception:
            self._free.append(slot)
            raise
        self.requests += 1
        if self.cengine.recurrent:
            self.state_resets += 1
        rec = _Slot(fut, max_new, queue,
                    stop=tuple(tuple(s) for s in
                               sampling.get("stop", ())))
        rec.meta = meta
        rec.sampling = sampling
        rec.aid = aid
        resumed = meta.resume is not None
        if resumed:
            rec.out = list(meta.resume["out"])
            rec.lps = list(meta.resume["lps"])
            rec.max_new = meta.resume["max_new"]
            meta.resume = None
        if self._ledger is not None:
            rec.block_charge = len(plan["fresh"])
            self._ledger.note_slot_taken(meta.tenant, rec.block_charge)
        rec.kv_toks = list(full)
        rec.node_refs = list(plan["chain"])
        cut = len(plan["chain"])
        rec.owned = {cut + i: blk
                     for i, blk in enumerate(plan["fresh"])}
        if plan["extra"] is not None:
            # read-only seed consumed (the copy is dispatched and
            # ordered before any later write by the donation chain)
            self._radix.unref([plan["extra"]])
        rec.prefilling = {"suffix": list(plan["suffix"]), "fed": 0}
        self._active[slot] = rec
        self._prefill_q.append(slot)
        self.tokens_reused += m
        if m > 0:
            self.prefix_hits += 1
        else:
            self.prefix_misses += 1
        ec = self.engine.ec
        self._temp[slot] = sampling.get("temperature", ec.temperature)
        self._topk[slot] = sampling.get("top_k", ec.top_k)
        self._topp[slot] = sampling.get("top_p", ec.top_p)
        self._sp_dirty = True
        if resumed:
            self.profiler.record("resume", 0.0)
        if meta.timeline is not None:
            meta.timeline.event(
                "resume" if resumed else "admit", slot=slot,
                prefill_computed=len(plan["suffix"]),
                prefill_reused=m)
        if not resumed and self.on_queue_wait is not None:
            try:
                self.on_queue_wait(self._clock() - meta.t_enqueue)
            except Exception:  # noqa: BLE001 — metrics hook
                pass

    async def _advance_prefills(self, loop) -> None:
        """Feed ONE budget-size slice of an unfinished chunked prefill
        through the fused append path. One slice per worker iteration
        bounds the decode stall at exactly the token budget; among
        waiting slots the slice goes to the SHORTEST REMAINING suffix
        (FIFO on ties), so a short interactive prompt that arrived
        behind a long bulk prefill finishes ahead of it instead of
        paying the whole bulk prompt in TTFT. Starvation is bounded:
        a long prefill competes only with already-admitted slots
        (at most max_slots - 1 of them), not the unbounded queue.
        The finishing slice samples the request's first token, unrefs
        the frozen flag, and indexes the now-complete prompt blocks in
        the radix tree, where a concurrent request can share them."""
        best = None
        for cand in list(self._prefill_q):
            crec = self._active.get(cand)
            if crec is None or crec.prefilling is None:
                self._prefill_q.remove(cand)  # retired underneath us
                continue
            if crec.fut.done():               # cancelled mid-prefill
                self._prefill_q.remove(cand)
                self._finish(cand, crec)
                continue
            left = (len(crec.prefilling["suffix"])
                    - crec.prefilling["fed"])
            if best is None or left < best[0]:
                best = (left, cand, crec)
        if best is None:
            return
        _, slot, rec = best
        pf = rec.prefilling
        s = self.prefill_chunk_tokens
        fed, suffix = pf["fed"], pf["suffix"]
        n = min(s, len(suffix) - fed)
        finish = fed + n == len(suffix)
        toks = np.zeros((1, s), np.int32)
        toks[0, :n] = suffix[fed:fed + n]
        sp = self._sp()

        def run_append(st=self._st, toks=toks, n=n, finish=finish,
                       slot=slot, sp=sp):
            # the enqueue, apart from the one blocking host sync below
            with jax.profiler.TraceAnnotation(
                    "dispatch.prefill_chunk", tokens=n,
                    finish=int(finish)):
                st, nxt, lp, rng = self.cengine.append_rows(
                    st, [slot], toks, [n], [finish], sp, self._rng)
            if finish:  # host-sync only the slice that samples
                return (st, int(np.asarray(nxt)[0]),
                        float(np.asarray(lp)[0]), rng)
            return st, None, None, rng

        t_slice = self._clock()
        with self.profiler.phase("prefill_chunk", tokens=n):
            async with self.gpu_lock:
                st, first, flp, rng = await loop.run_in_executor(
                    None, run_append)
                self._st = st
                self._rng = rng
        pf["fed"] = fed + n
        tl = rec.meta.timeline if rec.meta is not None else None
        if tl is not None and not tl.tokens:
            # the request's own slices up to its first token (a
            # preemption's replay is not part of it), against the time
            # it waited its turn for them (timeline.prefill_wait_s)
            tl.prefill_s += self._clock() - t_slice
            tl.prefill_slices += 1
        self.tokens_prefilled += n
        if not finish:
            return
        self._prefill_q.remove(slot)
        rec.prefilling = None
        self._index_inflight(rec)
        reused = len(rec.kv_toks) - len(suffix)
        if self.on_prefix is not None:
            try:
                self.on_prefix(
                    len(suffix), reused, reused > 0,
                    rec.meta.tenant if rec.meta is not None else "",
                    restored=(rec.meta.restored
                              if rec.meta is not None else 0))
            except Exception:  # noqa: BLE001 — metrics hook
                pass           # must never kill the worker
        if self.cengine.draft is not None and self.spec_enabled:
            with self.profiler.phase("draft"):
                await self._draft_seed(loop, slot, rec)
        self._emit(slot, rec, first, flp, decode=False)

    async def _draft_seed(self, loop, slot: int, rec: _Slot) -> None:
        """Seed the draft model's cache row for a freshly-admitted
        slot. Called BEFORE the first token is emitted, so the row
        holds exactly the prompt's KV and the draft cursor equals the
        target cursor — the alignment every speculative round
        preserves."""
        toks = list(rec.kv_toks)

        def run(dst=self._dst):
            if dst is None:
                dst = self.cengine.init_draft_slots()
            return self.cengine.draft_prefill(dst, slot, toks,
                                              self._rng)

        async with self.gpu_lock:
            dst, rng = await loop.run_in_executor(None, run)
            self._dst = dst
            self._rng = rng

    async def _spec_round(self, loop) -> None:
        """One speculative round for every live (non-frozen) slot:
        gamma draft proposals, one fused paged verify, then k+1 tokens
        emitted per row. Synchronous (no dispatch-ahead): acceptance
        counts gate retirement, so the host must observe each round
        before planning the next."""
        sp = self._sp()
        gamma = self.spec_gamma
        # cancelled (fut.done) rows stay IN the snapshot — the
        # detokenize loop below is where they get finished, exactly
        # like _process_chunk; only frozen rows are excluded
        snap = {s: r for s, r in self._active.items()
                if r.prefilling is None}
        if not snap:
            return

        def run_draft(st=self._st, dst=self._dst):
            return self.cengine.spec_draft(st, dst, sp, self._rng,
                                           gamma)

        with self.profiler.phase("draft", tokens=gamma * len(snap)):
            async with self.gpu_lock:
                dst, drafted, qs, rng = await loop.run_in_executor(
                    None, run_draft)
                self._dst = dst
                self._rng = rng

        def run_verify(st=self._st, dst=self._dst, drafted=drafted,
                       qs=qs):
            st, dst, emit, lps, k, rng = self.cengine.spec_verify(
                st, dst, drafted, qs, sp, self._rng, gamma)
            # host sync inside the executor, like every other dispatch
            return (st, dst, np.asarray(emit), np.asarray(lps),
                    np.asarray(k), rng)

        with self.profiler.phase("verify"):
            async with self.gpu_lock:
                st, dst, emit, lps, k, rng = \
                    await loop.run_in_executor(None, run_verify)
                self._st = st
                self._dst = dst
                self._rng = rng
        self.calls += 1
        round_proposed = gamma * len(snap)
        round_accepted = 0
        self.spec_proposed += round_proposed
        emitted0 = self.tokens_emitted
        with self.profiler.phase("detokenize"):
            for slot, srec in list(self._active.items()):
                if snap.get(slot) is not srec:
                    continue
                if srec.fut.done():  # cancelled mid-round
                    self._finish(slot, srec)
                    continue
                acc = int(k[slot])
                self.spec_accepted += acc
                round_accepted += acc
                for j in range(acc + 1):
                    self._emit(slot, srec, int(emit[slot, j]),
                               float(lps[slot, j]))
                    if slot not in self._active:
                        break  # retired mid-window; tail is dropped
        self.profiler.add_tokens("verify",
                                 self.tokens_emitted - emitted0)
        if self.on_spec_round is not None and round_proposed:
            try:
                self.on_spec_round(round_proposed, round_accepted)
            except Exception:
                pass  # hooks must never kill the worker

    def _plan_steps(self, inflight) -> int:
        """Next chunk size: bounded by the longest remaining budget NOT
        already covered by in-flight chunks (per slot — a slot admitted
        after a dispatch isn't covered by it). 0 = nothing useful to
        dispatch ahead."""
        if not self._active:
            return 0
        best = 0
        for slot, rec in self._active.items():
            if rec.prefilling is not None:
                continue  # frozen row: no decode budget yet
            cover = sum(r["steps"] for r in inflight
                        if r["snap"].get(slot) is rec)
            best = max(best, rec.max_new - len(rec.out) - cover)
        return min(self.chunk, best) if best > 0 else 0

    async def _dispatch_chunk(self, loop, steps: int) -> dict:
        """Dispatch one decode chunk WITHOUT host sync: device arrays
        come back as futures, the device starts computing, and the
        host keeps working. The snapshot maps slot -> the _Slot RECORD
        active at dispatch: chunk tokens are valid only for that exact
        request. Identity (not slot id) matters — a slot freed by a
        retirement and re-admitted while this chunk is in flight
        carries a NEW request whose tokens start with the next
        dispatch; emitting this chunk's row into it would corrupt its
        stream (caught by test_stop_sequences_retire_slots_early)."""
        sp = self._sp()
        # frozen (mid-chunked-prefill) rows are excluded at DISPATCH
        # time: the device masks them, so their chunk rows are garbage
        # even if they unfreeze while this chunk is in flight
        snap = {s: r for s, r in self._active.items()
                if r.prefilling is None}

        def run_step(st=self._st, sp=sp, steps=steps):
            # The rng chains THROUGH the compiled step (it splits
            # internally and returns the next key) — no host-side
            # jax.random.split dispatch per chunk.
            with jax.profiler.TraceAnnotation("dispatch.decode",
                                              steps=steps):
                return self.cengine.step(st, sp, self._rng, steps)

        if self.tracer is not None:
            # Tracer.wrap propagates the current context into the
            # executor thread, so the span nests under the request's
            # root when one is active.
            run_step = self.tracer.wrap(
                run_step, "decode.attention",
                impl=self.cengine.attention_impl, steps=steps)
        # `decode` phase = dispatch + any blocking inside run_step.
        # Tokens are attributed where they're OBSERVED (_process_chunk)
        # so over-decoded garbage rows never inflate the count. The
        # span says how far the KV walk of this dispatch's first step
        # follows the live blocks: a row's cursor is its last token's
        # cell, which that step writes.
        stats = self.cengine.decode_kv_steps(
            len(r.kv_toks) - 1 for r in snap.values())
        # the pool's minor dimension: the form its cells are kept in
        stats["kv_cell_lanes"] = self.cengine.kv_cell[1]
        if self.cengine.recurrent:
            # what the step reads and writes once beside the KV walk
            stats["ssm_state_bytes"] = (
                len(snap) * self.cengine.state_bytes_per_slot())
        with self.profiler.phase("decode", **stats):
            async with self.gpu_lock:
                st, toks, lps, rng = await loop.run_in_executor(
                    None, run_step)
                self._st = st
                self._rng = rng
        self.calls += steps
        return {"toks": toks, "lps": lps, "steps": steps, "snap": snap}

    @staticmethod
    async def _sync_chunk(loop, rec: dict) -> None:
        """Force a chunk's results to host (in the executor: jax
        dispatch is async and syncing on the loop thread would block
        the whole HTTP server for the device time)."""
        rec["toks"], rec["lps"] = await loop.run_in_executor(
            None, lambda: (np.asarray(rec["toks"]),
                           np.asarray(rec["lps"])))

    def _process_chunk(self, rec: dict) -> None:
        # `sample` = host materialization of the device's sampled
        # tokens; `detokenize` = per-token emit bookkeeping. Decode
        # TOKENS are booked here (each emitted token exactly once, so
        # preempt/resume replay — which RESTORES rec.out rather than
        # re-emitting — cannot double count).
        with self.profiler.phase("sample"):
            toks = np.asarray(rec["toks"])
            lps = np.asarray(rec["lps"])
        emitted0 = self.tokens_emitted
        with self.profiler.phase("detokenize"):
            for slot, srec in list(self._active.items()):
                if rec["snap"].get(slot) is not srec:
                    continue  # admitted after dispatch: not its tokens
                if srec.fut.done():  # caller cancelled mid-decode
                    self._finish(slot, srec)
                    continue
                for j in range(rec["steps"]):
                    self._emit(slot, srec, int(toks[slot, j]),
                               float(lps[slot, j]))
                    if slot not in self._active:
                        break  # retired mid-chunk; tail is trimmed
        self.profiler.add_tokens("decode",
                                 self.tokens_emitted - emitted0)

    async def _run(self) -> None:
        loop = asyncio.get_event_loop()
        # Chunks in flight on device, oldest first. Depth > 1 keeps the
        # chip busy while the host emits/retires the previous chunk.
        inflight: collections.deque = collections.deque()
        while True:
            if not self._active and not self._pending and not inflight:
                self._wake.clear()
                # `idle` (no work) is its own phase, excluded from the
                # goodput denominator — an empty batcher parked on its
                # wake event is not a bubble
                with self.profiler.phase("idle"):
                    await self._wake.wait()
            if self._halt:
                # migration export wants the batcher quiescent: park at
                # the loop boundary (active/pending intact, no local
                # buffers in flight) and let export_sequences serialize
                return
            # One profiled iteration: every explicit phase below claims
            # its wall time; end_iteration books the residual as
            # host_gap, so phase sums reconcile against loop wall time
            self.profiler.begin_iteration(
                active=len(self._active), prefilling=len(self._prefill_q),
                pending=len(self._pending), inflight=len(inflight),
                pool_in_use=self.cengine.pool.in_use)
            # Preemption runs BEFORE the dirty-slot reset so an evicted
            # slot's table is trash-reset in this same iteration —
            # admission below may hand its freed blocks to the
            # interactive request that triggered the eviction.
            if self._ledger is not None and self._pending:
                self._maybe_preempt()
            # Reset retired slots' block tables to trash BEFORE any
            # admission can hand their freed blocks to a new request:
            # the reset rides the state-donation chain, so it lands
            # after the retiree's last in-flight garbage writes and
            # before the new owner's adopt. (Slots re-admitted in the
            # same iteration are safe either way — adopt overwrites
            # the table — but an idle freed slot must stop writing.)
            if self._dirty and self._st is not None:
                dirty = sorted(set(self._dirty))
                try:
                    # slot recycling is part of the admission path's
                    # block management — attribute it there, not to the
                    # host_gap residual (its first call is also the
                    # reset program's compile)
                    with self.profiler.phase("admit"):
                        async with self.gpu_lock:
                            self._st = await loop.run_in_executor(
                                None, self.cengine.reset_slots,
                                self._st, dirty)
                except Exception as e:  # noqa: BLE001
                    self._fail_all(e)
                    inflight.clear()
                    continue
                self._dirty.clear()
            elif self._dirty:
                self._dirty.clear()  # no state left to reset
            # admit up to the free-slot count; dead futures are skipped
            if self._free and self._pending:
                take: list = []
                while self._pending and len(take) < len(self._free):
                    item = self._pending.popleft()
                    if item is None:
                        # fair-share queue: requests are waiting but
                        # every queued tenant is token-paced
                        break
                    if not item[3].done():
                        take.append(item)
                if take:
                    await self._admit_group(take)
                elif not self._active and not inflight and self._pending:
                    # nothing to decode and nothing admittable (all
                    # queued tenants paced): nap for the shortest
                    # refill instead of spinning the loop hot
                    delay = 0.05
                    if self._ledger is not None:
                        delay = min(max(
                            self._pending.pacing_delay(), 0.001), 0.05)
                    await asyncio.sleep(delay)
            # one prompt slice per iteration: the decode stall a
            # whole-prompt prefill would impose is chopped into
            # budget-size pieces interleaved with decode chunks
            try:
                await self._advance_prefills(loop)
            except Exception as e:  # noqa: BLE001
                self._fail_all(e)
                inflight.clear()
                continue
            try:
                # drain whatever already finished, without blocking.
                # INSIDE the try: an async-dispatched chunk that failed
                # on device reports ready and raises at materialization
                # — that must reach _fail_all like every other failure,
                # not kill the worker and hang every future.
                while inflight and inflight[0]["toks"].is_ready():
                    self._process_chunk(inflight.popleft())
                if self.cengine.draft is not None and self.spec_enabled:
                    # speculative rounds replace plain decode chunks;
                    # synchronous (acceptance gates retirement), so the
                    # inflight pipeline stays empty in spec mode
                    await self._spec_round(loop)
                    steps = 0
                else:
                    steps = self._plan_steps(inflight)
                if steps and len(inflight) < self.pipeline_depth:
                    inflight.append(
                        await self._dispatch_chunk(loop, steps))
                elif inflight:
                    # nothing useful to dispatch ahead: block on the
                    # oldest chunk and process it (the blocking wait IS
                    # device decode time: attribute it to `decode`)
                    head = inflight.popleft()
                    with self.profiler.phase("decode"):
                        await self._sync_chunk(loop, head)
                    self._process_chunk(head)
            except Exception as e:  # noqa: BLE001 — fail active requests
                self._fail_all(e)  # donated buffers may be mid-flight
                inflight.clear()
                continue
            self.profiler.note_pool(self.cengine.pool.in_use,
                                    self.cengine.pool.capacity)
            self.profiler.note_occupancy(
                len(self._active), len(self._active) + len(self._free))
            self.profiler.end_iteration()
            # let submissions/cancellations interleave between steps
            await asyncio.sleep(0)

    # -- migration / failover ---------------------------------------------

    def checkpoints(self) -> list[dict]:
        """Lightweight resume records (tokens only, no KV) for every
        admitted request — the crash-failover feed each fleet
        heartbeat carries to the router. `tokens` is the full replay
        prompt (original prompt incl. any registered-prefix expansion,
        plus every emitted token); a healthy peer resumes by
        re-prefilling `tokens` with budget `max_new - len(out)` —
        token-identical under greedy sampling, the same replay
        contract preemption relies on."""
        out: list[dict] = []
        for rec in self._active.values():
            if rec.fut.done() or rec.meta is None:
                continue
            out.append({
                "request_id": rec.meta.request_id,
                "tenant": rec.meta.tenant,
                "tokens": list(rec.kv_toks),
                "out": list(rec.out),
                "max_new": rec.max_new,
                "sampling": dict(rec.sampling or {}),
            })
        pending = (self._pending.items() if self._ledger is not None
                   else list(self._pending))
        for item in pending:
            tokens, max_new, sampling, fut, _q, _aid, meta = item
            if fut.done() or meta is None:
                continue
            emitted: list[int] = []
            if meta.resume is not None:
                # preempted-and-parked: tokens is already the replay
                # prompt (incl. emitted), budget is the original
                emitted = list(meta.resume["out"])
                max_new = meta.resume["max_new"]
            out.append({
                "request_id": meta.request_id,
                "tenant": meta.tenant,
                "tokens": list(tokens),
                "out": emitted,
                "max_new": max_new,
                "sampling": dict(sampling),
            })
        return out

    async def export_sequences(self) -> list[dict]:
        """Instant drain: stop admission, park the worker at a loop
        boundary, and serialize EVERY admitted request — active slots
        with their guaranteed-written full KV blocks, pending items
        tokens-only — into versioned migration wire records
        (serving.migration). Each exported future fails with
        `MigratedAway` (the router absorbs it and resumes on the
        peer); all blocks are released, so the replica can exit
        immediately instead of waiting out its longest generation."""
        self.cengine.refuse_recurrent(
            "export_sequences (a migrated block carries KV cells, not "
            "the state at its boundary)")
        self._draining = True
        w = self._worker
        if w is not None and not w.done():
            self._halt = True
            self._wake.set()
            try:
                await w
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
            finally:
                self._halt = False
        loop = asyncio.get_event_loop()
        ceng = self.cengine
        bs = ceng.block_size
        geometry = migration.pool_geometry(ceng)
        # Only full blocks strictly below len(kv_toks) - 1 are
        # guaranteed written (the final token's KV may still be in
        # flight from a discarded chunk) — the same line _cache_blocks
        # trusts. The tail re-prefills on the destination.
        exports = []
        tables = (np.asarray(self._st.block_table)
                  if self._st is not None else None)
        for slot, rec in list(self._active.items()):
            if rec.fut.done():
                self._release(slot)
                continue
            if rec.prefilling is not None:
                # mid-chunked-prefill: blocks past the fed frontier are
                # unwritten — export tokens-only, the peer re-prefills
                n_full = 0
            else:
                n_full = ((len(rec.kv_toks) - 1) // bs
                          if rec.kv_toks else 0)
            phys = ([int(b) for b in tables[slot][:n_full]]
                    if tables is not None and n_full > 0 else [])
            exports.append((slot, rec, phys))
        all_ids = [b for _, _, phys in exports for b in phys]
        k_host = v_host = None
        if all_ids:
            async with self.gpu_lock:
                k_host, v_host = await loop.run_in_executor(
                    None, ceng.export_blocks, self._st, all_ids)
        records: list[dict] = []
        off = 0
        for slot, rec, phys in exports:
            n = len(phys)
            kv = ((k_host[:, off:off + n], v_host[:, off:off + n])
                  if n else None)
            off += n
            meta = rec.meta
            rid = meta.request_id if meta is not None else ""
            records.append(migration.pack_record(
                request_id=rid,
                tenant=meta.tenant if meta is not None else "",
                ns=meta.ns if meta is not None else "",
                tokens=list(rec.kv_toks), out=list(rec.out),
                lps=list(rec.lps), max_new=rec.max_new,
                sampling=dict(rec.sampling or {}), geometry=geometry,
                kv=kv))
            if meta is not None and meta.timeline is not None:
                meta.timeline.event("migrate_out",
                                    emitted=len(rec.out), blocks=n)
            self._release(slot, cause="migration")
            self._fail(rec.fut, rec.queue, MigratedAway(rid))
        if self._ledger is not None:
            leftovers = self._pending.drain_all()
        else:
            leftovers = list(self._pending)
            self._pending.clear()
        for item in leftovers:
            tokens, max_new, sampling, fut, queue, _aid, meta = item
            if fut.done():
                continue
            out_toks: list[int] = []
            lps: list[float] = []
            if meta is not None and meta.resume is not None:
                out_toks = list(meta.resume["out"])
                lps = list(meta.resume["lps"])
                max_new = meta.resume["max_new"]
            rid = meta.request_id if meta is not None else ""
            records.append(migration.pack_record(
                request_id=rid,
                tenant=meta.tenant if meta is not None else "",
                ns=meta.ns if meta is not None else "",
                tokens=list(tokens), out=out_toks, lps=lps,
                max_new=max_new, sampling=dict(sampling),
                geometry=geometry,
                kv=None))
            if meta is not None and meta.timeline is not None:
                meta.timeline.event("migrate_out",
                                    emitted=len(out_toks), blocks=0)
            self._fail(fut, queue, MigratedAway(rid))
        return records

    async def import_sequence(self, record: dict, *,
                              wedge: bool = False) -> int:
        """Import one migrated sequence's KV blocks into the local
        pool and index them in the radix cache under the record's
        namespace — cache-WARM, not an orphan decode: the router
        re-issues the generation (`tokens`, remaining budget), which
        radix-hits the imported prefix and prefills only the tail.
        Returns the number of blocks the cache adopted (0 for
        tokens-only records or already-cached prefixes). Raises
        ValueError on wire/geometry mismatch. On ANY failure —
        including a wedged transfer (`wedge=True`, the chaos harness's
        mid-transfer fault) — every allocated block is freed back: a
        failed import must leak nothing."""
        self.cengine.refuse_recurrent(
            "import_sequence (a migrated block carries KV cells, not "
            "the state at its boundary)")
        rec = migration.unpack_record(record)
        migration.validate_geometry(rec["geometry"], self.cengine)
        if rec["kv"] is None:
            return 0
        k, v = migration.decode_kv(rec["kv"])
        n_full = int(k.shape[1])
        bs = self.cengine.block_size
        if n_full * bs > len(rec["tokens"]):
            raise ValueError(
                f"migration record claims {n_full} full blocks "
                f"({n_full * bs} cells) but carries only "
                f"{len(rec['tokens'])} tokens")
        pool = self.cengine.pool
        fresh = pool.alloc(n_full)
        if fresh is None:
            self._radix.evict(n_full - pool.num_free)
            fresh = pool.alloc(n_full)
            if fresh is None:
                raise RuntimeError(
                    f"migration import needs {n_full} blocks, pool "
                    f"has {pool.num_free} free")
        loop = asyncio.get_event_loop()
        done = False
        dup: list[int] = []
        try:
            if wedge:
                raise RuntimeError(
                    "migration transfer wedged (fault injection)")
            if self._st is None:
                self._st = self.cengine.init_slots()

            def run_import():
                # read self._st INSIDE the lock: import_blocks donates
                # the slot-state buffers, so a reference captured before
                # acquisition (another import, a decode step) would be
                # deleted by whoever held the lock first
                return self.cengine.import_blocks(
                    self._st, fresh, k, v)

            async with self.gpu_lock:
                self._st = await loop.run_in_executor(None, run_import)
            # index LAST: once the tree adopts a block it owns it, and
            # the rollback below must never free tree-owned blocks
            blocks = {i: b for i, b in enumerate(fresh)}
            adopted, _ = self._radix.insert(
                rec["tokens"][:n_full * bs], blocks, ns=rec["ns"])
            dup = [b for i, b in blocks.items() if i not in adopted]
            done = True
        finally:
            if not done:
                pool.free(fresh, cause="migration")
                if self._st is not None and any(
                        leaf.is_deleted() for leaf in
                        jax.tree.leaves(self._st)
                        if hasattr(leaf, "is_deleted")):
                    self._fail_all(RuntimeError(
                        "slot state lost to donated migration import"))
        if dup:
            # this prefix (or part of it) was already cached locally:
            # the tree kept its own blocks, ours are duplicates
            pool.free(dup, cause="divergence")
        return n_full - len(dup)

    async def export_prefix(self, tokens: list[int], *, ns: str = "",
                            request_id: str = "") -> dict | None:
        """Disaggregated prefill handoff (ISSUE 12): pack the cached
        full-block KV prefix of `tokens` into a migration wire record
        with `out=[]` — the prefill half of a prefill->decode handoff.
        The caller (the server's `:prefill` endpoint) pushes it to a
        decode peer's `/v1/migrate/in`; the peer's `import_sequence`
        indexes the blocks in its radix cache, so the re-issued
        generation radix-hits the prefix and only the partial tail
        block prefills there. Token-parity holds because radix reuse
        is bit-exact and the blocks travel in canonical form.

        Returns None when nothing is exportable (no cached full block
        for this prompt, or no device state yet) — the caller treats
        that as "skip the handoff", never as an error. Matched nodes
        are ref-pinned for the duration of the device->host copy so
        concurrent admission cannot evict them mid-export."""
        ceng = self.cengine
        ceng.refuse_recurrent(
            "export_prefix (a prefilled block carries KV cells, not the "
            "state at its boundary)")
        bs = ceng.block_size
        if self._st is None or len(tokens) < bs:
            return None
        nodes, _partial, _plen = self._radix.match(tokens, ns=ns)
        if not nodes:
            return None
        self._radix.ref(nodes)
        try:
            phys = [n.block for n in nodes]
            loop = asyncio.get_event_loop()
            async with self.gpu_lock:
                k_host, v_host = await loop.run_in_executor(
                    None, ceng.export_blocks, self._st, phys)
        finally:
            self._radix.unref(nodes)
        n_full = len(phys)
        return migration.pack_record(
            request_id=request_id, tenant="", ns=ns,
            tokens=[int(t) for t in tokens[:n_full * bs]],
            out=[], lps=[], max_new=0, sampling={},
            geometry=migration.pool_geometry(ceng),
            kv=(k_host, v_host))

    def in_flight(self) -> int:
        """Admitted-but-unfinished requests (pending, mid-prefill in
        the worker's local pipeline, or active in a slot). Zero means
        `close()` has nothing to abandon."""
        return self._admitted

    def begin_drain(self) -> None:
        """Stop admission (new `_enqueue` calls raise) while in-flight
        requests keep decoding to completion. Sticky until close() or
        end_drain()."""
        self._draining = True

    def end_drain(self) -> None:
        """Re-open admission after a completed drain. The reload path
        (`POST /v1/reload`) drains to zero, swaps weights, then calls
        this — a drain is only terminal when close() follows it."""
        self._draining = False

    def flush_cache(self) -> None:
        """Invalidate the radix prefix cache: after a weight swap every
        cached KV block describes activations of a model that no longer
        exists. Only safe at in_flight() == 0 — active sequences hold
        refs the clear would strand."""
        self._radix.clear(cause="refdrop")

    async def drain(self, timeout: float | None = None) -> bool:
        """Stop admission and wait for in-flight work to finish.
        Returns True when everything completed, False on timeout (or a
        dead worker with work still admitted) — the caller decides
        whether to close() anyway. Safe to call multiple times."""
        self._draining = True
        deadline = None if timeout is None else time.monotonic() + timeout
        while self._admitted > 0:
            if self._worker is None or self._worker.done():
                return False  # nobody left to finish the work
            if deadline is not None and time.monotonic() > deadline:
                return False
            await asyncio.sleep(0.01)
        return True

    async def close(self) -> None:
        self._closed = True
        if self._worker is not None:
            self._worker.cancel()
            try:
                await self._worker
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
        for slot, rec in list(self._active.items()):
            self._active.pop(slot, None)
            self._release_blocks(rec)
            if rec.queue is not None and not rec.fut.done():
                rec.queue.put_nowait(None)
            if not rec.fut.done():
                rec.fut.set_exception(RuntimeError("server shutting down"))
        if self._ledger is not None:
            leftovers = self._pending.drain_all()
        else:
            leftovers = list(self._pending)
            self._pending.clear()
        for item in leftovers:
            fut, queue = item[3], item[4]
            if queue is not None and not fut.done():
                queue.put_nowait(None)
            if not fut.done():
                fut.set_exception(RuntimeError("server shutting down"))
