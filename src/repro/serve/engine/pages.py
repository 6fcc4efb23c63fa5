"""Paged serving state: KV pages + fixed-size register slots, per sequence.

The engine's device-side state is one partitioned pytree per served model,
`{"kv": ..., "register": ...}`, because architectures carry two different
kinds of per-sequence state:

  * **kv** leaves grow with sequence length. They live in a single *page
    pool* per leaf — shape `[n_layers, n_pages, ...]`, each page in the
    tile-dense page format of `kernels.paged_attention` — with a
    host-side block table per sequence mapping logical positions to pages.
    Pages are allocated lazily as a sequence grows and freed on completion,
    so pool HBM is shared across sequences of very different lengths (the
    vLLM PagedAttention memory model). Dense/MoE attention caches are pure
    kv; a hybrid's shared-attention cache is its kv part.
  * **register** leaves are fixed-size per sequence — a Mamba2 layer's conv
    tail `[W-1, conv_dim]` and SSD state `[H, N, P]` do not grow with
    context. They live in *slot pools* — `[n_layers, n_slots, ...]` — and a
    sequence is assigned one register slot at admission, carried until
    release. No block table: the slot id indexes axis 1 of every register
    leaf directly. Pure-SSM models are all register; hybrids mix both kinds
    in one state pytree.

The kv data path is block-table-native: the scheduler hands the pool and
the per-sequence block-table rows straight to the backend's
`forward_chunk`, which scatters each new KV row into its page and attends
by walking the table inside `kernels.ops.paged_attention` (one Mosaic
kernel on TPU: the page ids are scalar-prefetched and each page is DMA'd
into VMEM exactly once, with online softmax across the walk). No
contiguous slab is ever materialised. Register leaves are gathered by slot
index at the top of the forward and scattered back once per call.

Both pools are format-agnostic: they are built by the adapter's
`init_state(n_pages, page_size, n_slots)` — the page/slot axis is axis 1
of every leaf, after the layer axis — so the same machinery (scrub,
copy-on-write, swap, the host mirror) pages the float cache ({kv_rows}),
the asymmetric per-(position, head) int8/int4 KV cache (codes *and*
their scale/zero rows, {kv_rows, kv_scale_zero}), and the SSM conv/SSD
slot pools.

This module keeps the *bookkeeping*: the two allocators, block tables and
register-slot maps, and release-time scrubbing. KV pages are
**refcounted** so many sequences — and the radix prefix cache
(`radix.RadixCache`) — can point at the same immutable prefix page:
`alloc()` hands out pages at refcount 1, `incref()` adds a holder, and
`free()` *decrements*, returning a page to the free list only when its
count hits zero (the list of pages that actually dropped to zero is
`free()`'s return value). The refcount/copy-on-write contract is:

  * a page is only ever *written* by a holder that owns it exclusively
    (refcount 1): freshly-allocated pages, or a private copy made by the
    scheduler's copy-on-write dispatch before extending a shared page;
  * shared pages (refcount > 1) are immutable until every holder has
    dropped its reference — so releasing one sharer can never perturb
    the bits another sharer (or the prefix tree) is still reading;
  * **scrub-on-release applies only to exclusively-owned state**: the
    fused `scrub()` dispatch zeroes exactly the pages `free()` reported
    as dropping to refcount 0, plus the released register slot. Zeroing
    a still-referenced page would corrupt live readers; skipping the
    zero on an exclusively-freed one would leak state into its next
    owner (load-bearing for register slots, defence in depth for KV).

Register slots are *excluded* from all sharing: SSM conv/SSD state is a
position-dependent running summary, not an addressable prefix, so a slot
always has exactly one owner and is scrubbed on every release.

The same `release()`/`scrub()` path serves normal completion,
cancellation, and preemption — a preempted victim's shared pages are
simply unpinned (deref'd, never scrubbed) while its exclusive pages are
zeroed and returned; its state is either recomputed later by replaying
the host-known token stream, or parked in the **host swap tier** (below)
and copied back at re-admission. `release(rid, adopted=k)` lets the
prefix tree take over the request's reference on its first `k` pages
instead of dropping them. `alloc()` validates before mutating:
`MemoryError` on exhaustion leaves the free list untouched, which is
what lets the scheduler evict cached prefixes or preempt a victim and
simply retry. Each release scrubs through ONE fused jit dispatch (pages
of every kv leaf + the register slot together, page counts padded to
powers of two to bound the jit variants), tallied as `scrub_state` in
the `kernels.ops` dispatch counts. The legacy `gather_pages` /
`scatter_decode_rows` primitives survive purely as test oracles.

**Two-tier residency.** When a `HostSwapPool` is attached (an
engine-configured host-memory budget, `--swap-host-mb`), a KV page has
one of three residencies:

  * **device** — a plain `int` page id in the block table, readable by
    every fused dispatch; the only residency the kernels ever see.
  * **host** — a `HostPageRef` table entry naming a slot of the pool's
    numpy mirror (one buffer per kv leaf, shaped like the device pool
    with the page axis sized to the budget). The device copy was
    scrubbed and returned to the allocator; the bytes live only on host.
  * **in-flight** — a device page id currently inside a swap transfer
    window (`PagedKVCache._inflight`). Scrub and copy-on-write assert
    against touching it, so a transfer can never race state maintenance.

`swap_out(rid)` moves exactly the victim's *exclusively-held* device
pages (refcount 1) to host slots — one fused gather dispatch + one
`device_get`, tallied as `swap_out` — then derefs them so the device
copies scrub and return to the pool. Shared pages (radix tree or sibling
sequences hold references) keep the victim's reference and stay device
resident: a radix-shared page is therefore swapped at most once — in
practice never, because tree-held prefixes are live device state other
sequences still read — and a copy-on-write source is always device
resident, never a `HostPageRef`. `swap_in(rid, alloc_fn)` allocates
fresh device pages first (so `MemoryError` mutates nothing), copies the
host slots back through one `device_put` + fused scatter (`swap_in`),
patches the block-table row in place, and releases the host slots. The
bytes moved per page (`page_bytes`, from the adapter's state-spec
dtypes) are what the scheduler's swap-vs-replay cost rule weighs against
re-prefill tokens — quantized int4/int8 KV pages cost 4-8x less traffic
per page than bf16, which is exactly what tips the rule toward swap.

Page 0 / slot 0 are reserved as scratch: padded batch rows (inactive
slots) and padded block-table entries point at them, so their masked
reads and dead writes can never touch a live sequence's state (which is
also what makes scratch-padded scrub index vectors harmless).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.kernels.paged_attention import pack_pages, unpack_pages

Params = dict[str, Any]

SCRATCH_PAGE = 0
SCRATCH_SLOT = 0


def pages_for(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold `n_tokens` KV rows."""
    return -(-n_tokens // page_size)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _scrub_impl(state: Params, page_idx: jnp.ndarray, slot: jnp.ndarray,
                *, do_slot: bool) -> Params:
    """One fused dispatch zeroing `page_idx` rows of every kv leaf and —
    when `do_slot` — slot `slot` of every register leaf. `page_idx` may
    be scratch-padded (zeroing the scratch page is a harmless dead
    write); `slot` is scratch when only pages are scrubbed."""
    kv = jax.tree.map(
        lambda a: a.at[:, page_idx].set(jnp.zeros((), a.dtype)),
        state["kv"])
    register = state["register"]
    if do_slot:
        register = jax.tree.map(
            lambda a: a.at[:, slot].set(jnp.zeros((), a.dtype)), register)
    return {"kv": kv, "register": register}


def _cow_impl(state: Params, src: jnp.ndarray, dst: jnp.ndarray) -> Params:
    """Copy page `src` into page `dst` on every kv leaf (one dispatch)."""
    return {"kv": jax.tree.map(
        lambda a: a.at[:, dst].set(
            jax.lax.dynamic_index_in_dim(a, src, axis=1, keepdims=False)),
        state["kv"]), "register": state["register"]}


def _swap_gather_impl(state: Params, page_idx: jnp.ndarray) -> Params:
    """Gather `page_idx` rows of every kv leaf into contiguous blocks —
    the device half of swap-out, fused into one dispatch so a victim's
    whole page set leaves in a single `device_get`. `page_idx` may be
    scratch-padded (the extra rows are sliced off host-side)."""
    return jax.tree.map(lambda a: a[:, page_idx], state["kv"])


def _swap_scatter_impl(state: Params, blocks: Params,
                       page_idx: jnp.ndarray) -> Params:
    """Scatter host blocks back into `page_idx` rows of every kv leaf —
    the device half of swap-in, one fused dispatch over a single
    `device_put`. Pad entries target the scratch page with zero blocks
    (dead writes by the scratch contract)."""
    return {"kv": jax.tree.map(
        lambda a, b: a.at[:, page_idx].set(b.astype(a.dtype)),
        state["kv"], blocks), "register": state["register"]}


class HostPageRef:
    """Block-table entry for a host-resident page: names a slot of the
    `HostSwapPool` mirror instead of a device page id. Kernels never see
    one — `block_table_array` refuses to serialize a table holding any —
    so a sequence with host-resident pages must swap in before dispatch.
    """

    __slots__ = ("slot",)

    def __init__(self, slot: int):
        self.slot = slot

    def __repr__(self):
        return f"HostPageRef({self.slot})"


class HostSwapPool:
    """Host-memory mirror of the device kv page pool — the swap tier.

    One numpy buffer per kv leaf, shaped like the device pool with the
    page axis resized to the budget: `[n_layers, n_slots, ...]`.
    Capacity is derived from a byte budget and the per-page byte cost of
    the adapter's state spec (quantized page formats shrink it
    4-8x, which is what makes offload cheaper than recompute). Slots are
    a plain free list — host pages are never shared (only exclusively
    held device pages are ever swapped out), so there is no refcounting
    and no scratch slot on this tier.
    """

    def __init__(self, kv_template: Params, budget_bytes: int):
        if budget_bytes < 0:
            raise ValueError("host swap budget must be >= 0 bytes")
        leaves = jax.tree.leaves(kv_template)
        self.page_bytes = sum(
            a.shape[0] * int(np.prod(a.shape[2:], dtype=np.int64))
            * np.dtype(a.dtype).itemsize for a in leaves)
        self.capacity = (int(budget_bytes // self.page_bytes)
                         if self.page_bytes else 0)
        self.buf = jax.tree.map(
            lambda a: np.zeros((a.shape[0], self.capacity) + tuple(a.shape[2:]),
                               np.dtype(a.dtype)), kv_template)
        self._free = list(range(self.capacity - 1, -1, -1))
        self._free_set = set(self._free)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.capacity - len(self._free)

    def take(self, n: int) -> list[int]:
        """Claim `n` host slots (validated before mutating, like
        `PageAllocator.alloc`: `MemoryError` leaves the free list whole)."""
        if n > len(self._free):
            raise MemoryError(f"host swap tier exhausted: need {n}, "
                              f"free {len(self._free)}")
        out = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(out)
        return out

    def store(self, slots: list[int], blocks: Params):
        """Copy gathered page blocks (`[n_layers, len(slots), ...]` per
        leaf, already on host) into the claimed slots."""
        idx = np.asarray(slots, np.int64)
        for buf, b in zip(jax.tree.leaves(self.buf),
                          jax.tree.leaves(blocks)):
            buf[:, idx] = b

    def load(self, slots: list[int]) -> Params:
        """Read the slots back as contiguous blocks (numpy views stacked
        per leaf), ready for one `device_put`."""
        idx = np.asarray(slots, np.int64)
        return jax.tree.map(lambda buf: buf[:, idx], self.buf)

    def release(self, slots: list[int]):
        """Return slots to the free list (validated as a batch first)."""
        batch = set()
        for s in slots:
            if s < 0 or s >= self.capacity or s in self._free_set \
                    or s in batch:
                raise ValueError(f"double/invalid release of host slot {s}")
            batch.add(s)
        self._free.extend(slots)
        self._free_set.update(slots)


class PageAllocator:
    """Host-side refcounted free-list allocator over pool pages (page 0
    reserved).

    A membership *set* shadows the LIFO stack so the double-free guard is
    O(1) per page instead of an O(n) list scan — freeing a long sequence's
    pages used to be quadratic in pool size. Every allocated page carries
    a reference count (`alloc` → 1, `incref` adds holders); `free`
    decrements and a page returns to the free list only at count zero, so
    prefix-shared pages survive until their last holder lets go.
    """

    def __init__(self, n_pages: int):
        if n_pages < 2:
            raise ValueError("pool needs at least 2 pages (page 0 is scratch)")
        self.n_pages = n_pages
        self._free = list(range(n_pages - 1, SCRATCH_PAGE, -1))
        self._free_set = set(self._free)
        self._refs: dict[int, int] = {}   # page → holders (allocated only)
        # telemetry: high-water mark of pages simultaneously in use (the
        # utilization headroom number the metrics snapshot reports)
        self.peak_in_use = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.capacity - len(self._free)

    @property
    def capacity(self) -> int:
        """Allocatable pages (excludes the scratch page)."""
        return self.n_pages - 1

    def reset_peak(self):
        """Restart the high-water mark at the current level (measurement
        window boundary, used by `ServeEngine.reset_metrics`)."""
        self.peak_in_use = self.in_use

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise MemoryError(f"page pool exhausted: need {n}, "
                              f"free {len(self._free)}")
        out = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(out)
        for p in out:
            self._refs[p] = 1
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return out

    def incref(self, pages: list[int]):
        """Add one holder to each (allocated) page — validated as a
        batch before mutating, like `free`."""
        for p in pages:
            if p <= SCRATCH_PAGE or p >= self.n_pages \
                    or p in self._free_set:
                raise ValueError(f"incref of unallocated page {p}")
        for p in pages:
            self._refs[p] += 1

    def refcount(self, page: int) -> int:
        """Current holder count (0 for free pages)."""
        return self._refs.get(page, 0) if page not in self._free_set else 0

    @property
    def n_shared(self) -> int:
        """Pages currently held by more than one owner (telemetry)."""
        return sum(1 for c in self._refs.values() if c > 1)

    def free(self, pages: list[int]) -> list[int]:
        """Drop one reference per page; returns the pages whose count hit
        zero and were actually returned to the free list (exactly the set
        the caller must scrub — still-shared pages stay live and
        untouched)."""
        # validate the whole batch (including intra-batch duplicates)
        # before mutating, so a raise leaves the allocator consistent
        batch = set()
        for p in pages:
            if p <= SCRATCH_PAGE or p >= self.n_pages \
                    or p in self._free_set or p in batch:
                raise ValueError(f"double/invalid free of page {p}")
            batch.add(p)
        freed = []
        for p in pages:
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                freed.append(p)
        self._free.extend(freed)
        self._free_set.update(freed)
        return freed


class RegisterAllocator:
    """Free-list allocator over register slots — the `PageAllocator`
    sibling for the fixed-size state kind (slot 0 reserved as scratch).

    A sequence holds exactly one slot for its whole lifetime, so slots are
    allocated/freed one at a time and capacity equals the engine's
    max-concurrent-sequences bound.
    """

    def __init__(self, n_slots: int):
        if n_slots < 2:
            raise ValueError("register pool needs at least 2 slots "
                             "(slot 0 is scratch)")
        self.n_slots = n_slots
        self._free = list(range(n_slots - 1, SCRATCH_SLOT, -1))
        self.peak_in_use = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.capacity - len(self._free)

    @property
    def capacity(self) -> int:
        """Allocatable slots (excludes the scratch slot)."""
        return self.n_slots - 1

    def reset_peak(self):
        self.peak_in_use = self.in_use

    def alloc(self) -> int:
        if not self._free:
            raise MemoryError("register slots exhausted")
        out = self._free.pop()
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return out

    def free(self, slot: int):
        if slot <= SCRATCH_SLOT or slot >= self.n_slots \
                or slot in self._free:
            raise ValueError(f"double/invalid free of register slot {slot}")
        self._free.append(slot)


@functools.partial(jax.jit, static_argnames="kv_heads")
def gather_pages(pool: Params, block_tables: jnp.ndarray, *,
                 kv_heads: int) -> Params:
    """Gather pages into contiguous per-sequence slabs (TEST ORACLE ONLY —
    the serving path is block-table-native and never materialises slabs).

    pool leaves: [n_layers, n_pages, ...] in the page format of
    `kernels.paged_attention`, with `kv_heads` KV heads
    block_tables: [B, P] int32 page ids (pad entries = SCRATCH_PAGE)
    returns the same format with one page of P·page_size positions per
    sequence ([n_layers, B, ...]: a dense cache `unpack_pages` reads).
    """
    b, p = block_tables.shape

    def g(a):
        s = jnp.take(a, block_tables.reshape(-1), axis=1)
        return s.reshape(a.shape[0], b, p * a.shape[2], *a.shape[3:])

    k, v, aux = unpack_pages(pool, kv_heads)
    return pack_pages(g(k), g(v), None if aux is None else tuple(map(g, aux)))


@functools.partial(jax.jit, static_argnames="kv_heads")
def scatter_decode_rows(pool: Params, slab: Params, fill_pos: jnp.ndarray,
                        page_ids: jnp.ndarray, offsets: jnp.ndarray, *,
                        kv_heads: int) -> Params:
    """Write each slot's newly decoded KV row back into its page (TEST
    ORACLE ONLY — the forward writes rows in place on the serving path).

    Extracts position `fill_pos[i]` of slot i from the slab (the format
    `gather_pages` returns) and stores it at (page_ids[i], offsets[i]) in
    the pool. Padded slots point at the scratch page, so their
    (duplicate) writes are harmless.
    """
    rows = jnp.arange(fill_pos.shape[0])
    k, v, aux = unpack_pages(slab, kv_heads)
    pk, pv, paux = unpack_pages(pool, kv_heads)

    def put(dst, src):
        return dst.at[:, page_ids, offsets].set(
            src[:, rows, fill_pos].astype(dst.dtype))

    return pack_pages(put(pk, k), put(pv, v),
                      None if aux is None
                      else tuple(put(d, s) for d, s in zip(paux, aux)))


class PagedKVCache:
    """Partitioned state + allocators + per-sequence block tables and
    register-slot map for one served model.

    `state` is the `{"kv": ..., "register": ...}` pytree the adapter's
    `init_state` built (a bare kv pool is accepted and wrapped, for the
    test oracles that only exercise the kv bookkeeping). `pool` aliases
    `state["kv"]` for the kv-only callers.
    """

    def __init__(self, state: Params, n_pages: int, page_size: int,
                 n_slots: int = 0):
        if not (isinstance(state, dict) and set(state) == {"kv", "register"}):
            state = {"kv": state, "register": {}}
        self.state = state
        self.page_size = page_size
        self.allocator = PageAllocator(n_pages)
        # table entries are device page ids (int) or HostPageRef — the
        # per-page residency ledger lives in the tables themselves
        self.tables: dict[int, list[int | HostPageRef]] = {}
        self.has_register = bool(jax.tree.leaves(state["register"]))
        self.registers = RegisterAllocator(n_slots) if self.has_register \
            else None
        self.slots: dict[int, int] = {}
        # host swap tier: absent until the engine attaches a budget
        self.host_pool: HostSwapPool | None = None
        # device page ids inside a swap-transfer window right now; scrub
        # and cow assert against touching them
        self._inflight: set[int] = set()
        # bytes one page costs across every kv leaf (the swap cost unit)
        self.page_bytes = sum(
            a.shape[0] * int(np.prod(a.shape[2:], dtype=np.int64))
            * np.dtype(a.dtype).itemsize
            for a in jax.tree.leaves(state["kv"]))
        # telemetry: release-time scrub totals (pages / register slots
        # zeroed) and swap traffic, mirrored into the metrics snapshot
        self.pages_scrubbed = 0
        self.slots_scrubbed = 0
        self.pages_swapped_out = 0
        self.pages_swapped_in = 0
        # fused state-maintenance dispatches, compiled once per padded
        # page-count (scrub) and once at all (cow); both donate the state
        # so a pool sized to fill HBM never needs a second live copy
        self._scrub_jit = jax.jit(_scrub_impl, donate_argnums=(0,),
                                  static_argnames=("do_slot",))
        self._cow_jit = jax.jit(_cow_impl, donate_argnums=(0,))
        # swap transfers: the gather reads (state survives for the deref
        # that follows), the scatter donates like scrub
        self._swap_gather_jit = jax.jit(_swap_gather_impl)
        self._swap_scatter_jit = jax.jit(_swap_scatter_impl,
                                         donate_argnums=(0,))

    @property
    def pool(self) -> Params:
        return self.state["kv"]

    @pool.setter
    def pool(self, value: Params):
        self.state["kv"] = value

    def open(self, rid: int):
        if rid in self.tables:
            raise ValueError(f"sequence {rid} already open")
        self.tables[rid] = []
        if self.registers is not None:
            self.slots[rid] = self.registers.alloc()

    def ensure(self, rid: int, n_tokens: int):
        """Grow `rid`'s block table to cover `n_tokens` positions."""
        table = self.tables[rid]
        need = pages_for(n_tokens, self.page_size) - len(table)
        if need > 0:
            table.extend(self.allocator.alloc(need))

    def release(self, rid: int, adopted: int = 0):
        """Return `rid`'s pages and register slot. The first `adopted`
        table entries' references were taken over by another holder (the
        radix prefix tree) and are skipped; the rest are deref'd, and
        only pages that dropped to refcount 0 are scrubbed — together
        with the register slot — in one fused dispatch. Host-resident
        entries (a swapped-out sequence being cancelled, expired, or
        degraded to replay) have no device reference: their host slots
        are simply returned to the swap tier."""
        entries = self.tables.pop(rid)[adopted:]
        slot = self.slots.pop(rid, None)
        self.deref([p for p in entries if isinstance(p, int)], slot)
        host_slots = [e.slot for e in entries
                      if isinstance(e, HostPageRef)]
        if host_slots:
            self.host_pool.release(host_slots)
        if slot is not None:
            self.registers.free(slot)

    def deref(self, pages: list[int], slot: int | None = None):
        """Drop one reference per page; scrub whatever actually freed
        (refcount hit 0) plus `slot`, in one fused dispatch."""
        freed = self.allocator.free(pages)
        self.scrub(freed, slot)

    def scrub(self, pages: list[int], slot: int | None):
        """Zero released state rows of BOTH kinds — in ONE fused jit
        dispatch per call — so a recycled page or slot can never leak its
        predecessor's state.

        For register leaves this is load-bearing: the next sequence reads
        its slot's full state at admission (the SSM carried conv/SSD
        state), so stale rows would silently contaminate it. Freed KV
        pages are only ever re-read after being overwritten (the causal
        mask / seq_lengths hide rows past the fill point), so their zeroing
        is defence in depth through the same method.

        Callers must pass only *exclusively-owned* state: the engine
        hands in exactly the pages `PageAllocator.free` reported as
        dropping to refcount 0 — scrubbing a still-shared page would
        corrupt every surviving holder. Page indices are padded to the
        next power of two with the scratch page (whose content is
        garbage by contract, so the dead extra zeroing is harmless and
        the jit variant count stays bounded); the whole call is tallied
        as one `scrub_state` dispatch in the `kernels.ops` counts.
        """
        bad = set(pages) & self._inflight
        assert not bad, f"scrub of in-flight swap page(s) {sorted(bad)}"
        for p in pages:
            assert self.allocator.refcount(p) == 0, \
                f"scrub of still-referenced page {p}"
        has_kv = bool(pages) and bool(jax.tree.leaves(self.state["kv"]))
        do_slot = slot is not None \
            and bool(jax.tree.leaves(self.state["register"]))
        if not has_kv and not do_slot:
            return
        padded = _next_pow2(len(pages)) if has_kv else 1
        idx = jnp.asarray(
            (pages + [SCRATCH_PAGE] * (padded - len(pages))) if has_kv
            else [SCRATCH_PAGE], jnp.int32)
        kops._record_dispatch("scrub_state")
        self.state = self._scrub_jit(
            self.state, idx,
            jnp.asarray(slot if do_slot else SCRATCH_SLOT, jnp.int32),
            do_slot=do_slot)
        if has_kv:
            self.pages_scrubbed += len(pages)
        if slot is not None:
            self.slots_scrubbed += 1

    def cow_copy(self, src: int, dst: int):
        """Copy-on-write primitive: duplicate page `src` into `dst`
        across every kv leaf in one fused dispatch (tallied as
        `cow_page_copy`). The caller owns `dst` exclusively and may then
        overwrite rows past the shared prefix without perturbing `src`'s
        other holders. Both ends must be live device pages: a host
        resident page has no device id at all, so a `HostPageRef` can
        never reach here — the asserts pin the residency contract (COW
        never targets a swapped or in-flight source)."""
        assert src not in self._inflight and dst not in self._inflight, \
            f"cow touching in-flight swap page ({src} -> {dst})"
        assert self.allocator.refcount(src) >= 1, \
            f"cow from unallocated (or host-resident) page {src}"
        kops._record_dispatch("cow_page_copy")
        self.state = self._cow_jit(self.state, jnp.asarray(src, jnp.int32),
                                   jnp.asarray(dst, jnp.int32))

    # ------------------------------------------------------------------
    # host swap tier
    # ------------------------------------------------------------------

    def attach_host_pool(self, host_mb: float) -> HostSwapPool:
        """Create the host swap tier under a `host_mb` MiB budget (page
        capacity = budget // page_bytes; a budget smaller than one page
        yields capacity 0, gracefully disabling swap-out)."""
        self.host_pool = HostSwapPool(self.state["kv"],
                                      int(host_mb * 2 ** 20))
        return self.host_pool

    def residency(self, rid: int) -> list[str]:
        """Per-table-entry residency of `rid`: "device", "host", or
        "in_flight" (the ledger view the tests and probes read)."""
        out = []
        for e in self.tables[rid]:
            if isinstance(e, HostPageRef):
                out.append("host")
            elif e in self._inflight:
                out.append("in_flight")
            else:
                out.append("device")
        return out

    def swap_eligible_pages(self, rid: int) -> list[int]:
        """Device pages of `rid` that swap-out would move: exactly the
        exclusively-held ones (refcount 1). Shared pages — radix-tree or
        sibling references — keep the victim's retained ref and stay
        device resident, so a shared page swaps at most once and a COW
        source is never host resident."""
        alloc = self.allocator
        return [p for p in self.tables[rid]
                if isinstance(p, int) and alloc.refcount(p) == 1]

    def swap_out(self, rid: int) -> tuple[int, int]:
        """Move `rid`'s exclusively-held pages to the host tier; returns
        `(pages_moved, bytes_moved)`.

        One fused gather dispatch (tallied `swap_out`) + one
        `device_get` moves the whole set; the table entries become
        `HostPageRef`s in place and the device copies are deref'd —
        dropping the sole reference, so they scrub and return to the
        allocator. Host slots are claimed *before* the transfer
        (`MemoryError` on an over-budget tier mutates nothing)."""
        if self.host_pool is None:
            raise RuntimeError("no host swap pool attached")
        table = self.tables[rid]
        moved = [(i, p) for i, p in enumerate(table)
                 if isinstance(p, int) and self.allocator.refcount(p) == 1]
        if not moved:
            return 0, 0
        pages = [p for _, p in moved]
        slots = self.host_pool.take(len(pages))
        padded = _next_pow2(len(pages))
        idx = jnp.asarray(pages + [SCRATCH_PAGE] * (padded - len(pages)),
                          jnp.int32)
        self._inflight.update(pages)
        try:
            kops._record_dispatch("swap_out")
            blocks = jax.device_get(self._swap_gather_jit(self.state, idx))
            self.host_pool.store(
                slots, jax.tree.map(lambda a: a[:, :len(pages)], blocks))
        finally:
            self._inflight.difference_update(pages)
        for (i, _), s in zip(moved, slots):
            table[i] = HostPageRef(s)
        self.deref(pages)
        self.pages_swapped_out += len(pages)
        return len(pages), len(pages) * self.page_bytes

    def swap_in(self, rid: int,
                alloc_fn: Callable[[int], list[int]] | None = None
                ) -> tuple[int, int]:
        """Restore `rid`'s host-resident pages to the device tier;
        returns `(pages_moved, bytes_moved)`.

        Fresh device pages are allocated first — through `alloc_fn` when
        the caller has a smarter allocator (the scheduler's tree-evicting
        one) — so a `MemoryError` leaves table, host tier, and allocator
        untouched. One `device_put` + fused scatter dispatch (tallied
        `swap_in`) writes the blocks back, the block-table row is patched
        in place (bit-identical continuation: the pages hold the same
        rows they held before swap-out), and the host slots are freed."""
        table = self.tables[rid]
        refs = [(i, e) for i, e in enumerate(table)
                if isinstance(e, HostPageRef)]
        if not refs:
            return 0, 0
        slots = [e.slot for _, e in refs]
        new_pages = (alloc_fn or self.allocator.alloc)(len(refs))
        pad = _next_pow2(len(new_pages)) - len(new_pages)
        blocks = self.host_pool.load(slots)
        if pad:
            blocks = jax.tree.map(
                lambda b: np.concatenate(
                    [b, np.zeros((b.shape[0], pad) + b.shape[2:], b.dtype)],
                    axis=1), blocks)
        idx = jnp.asarray([p for p in new_pages]
                          + [SCRATCH_PAGE] * pad, jnp.int32)
        self._inflight.update(new_pages)
        try:
            kops._record_dispatch("swap_in")
            self.state = self._swap_scatter_jit(
                self.state, jax.device_put(blocks), idx)
        finally:
            self._inflight.difference_update(new_pages)
        for (i, _), p in zip(refs, new_pages):
            table[i] = p
        self.host_pool.release(slots)
        self.pages_swapped_in += len(refs)
        return len(refs), len(refs) * self.page_bytes

    def page_of(self, rid: int, position: int) -> tuple[int, int]:
        """(page id, in-page offset) holding `position` of sequence `rid`."""
        return (self.tables[rid][position // self.page_size],
                position % self.page_size)

    def block_table_array(self, rids: list[int | None],
                          n_cols: int) -> jnp.ndarray:
        """[len(rids), n_cols] int32 table, short rows padded with scratch.
        `None` entries are padded batch rows (all-scratch).

        A row longer than `n_cols` is an error, never a silent truncation:
        a too-narrow table would drop live pages from the kernel's walk
        (and from the write targeting) without any visible failure.
        """
        bt = [self.tables[r] if r is not None else [] for r in rids]
        for r, row in zip(rids, bt):
            if len(row) > n_cols:
                raise ValueError(
                    f"block table for sequence {r} holds {len(row)} pages "
                    f"but only {n_cols} columns were requested")
            if any(not isinstance(p, int) for p in row):
                raise ValueError(
                    f"sequence {r} has host-resident pages; it must swap "
                    f"in before any kernel dispatch")
        bt = [row + [SCRATCH_PAGE] * (n_cols - len(row)) for row in bt]
        return jnp.asarray(bt, jnp.int32)

    def register_index_array(self, rids: list[int | None]) -> jnp.ndarray:
        """[len(rids)] int32 register slot per batch row; `None` (padded)
        rows point at the scratch slot, so their dead writes never touch a
        live sequence's state."""
        return jnp.asarray(
            [self.slots[r] if r is not None else SCRATCH_SLOT for r in rids],
            jnp.int32)
