"""Asynchronous multi-device PIC engine — the paper's async(n) queues in JAX.

The paper (§4) overlaps particle migration with compute by splitting each
GPU's particles across ``async(n)`` OpenACC queues / OpenMP ``nowait`` tasks
with ``depend`` clauses: while queue *k*'s MPI exchange is on the wire,
queue *k+1* runs the mover. The JAX mapping:

* a **queue** is an interleaved slice of the stacked (S, cap) particle
  buffer (slot ``c`` belongs to queue ``c % async_n``, so the initial
  contiguous live block spreads evenly);
* queue *k*'s migration ``ppermute`` is issued immediately after its fused
  push, and queue *k+1*'s push has **no data dependency** on it — XLA's
  latency-hiding scheduler overlaps the collective with the next push,
  exactly what ``nowait`` buys the paper (and what CUDA streams buy its
  multi-GPU version);
* the received packs are **double-buffered**: they are held as live values
  (``depend(in)`` edges) while later queues compute, and claim their landing
  slots only after every queue of every species group has been pushed.

The per-step phase order matches BIT1's cycle, with one JAX-native addition:
ingest (scatter last step's arrivals + births, periodic/skew-triggered queue
rebalance — ``cell_order=True`` makes the rebalance a counting sort by cell)
-> halo field solve (see ``halo.py`` — no full-rho all_gather) -> per-queue
fused push+deposit -> per-queue binary collisions (the ``collide`` phase:
cell-binned elastic / charge-exchange / Coulomb pairing inside the queue
slice — velocities only, so no ring traffic) -> in-queue MC ionization ->
per-queue migration exchange + SEE -> deferred merge -> diagnostics psum.

Free-slot ring (the merge-phase fix): the seed merge re-discovered dead
slots with one full-capacity ``free_slots`` scan per species per step, so
the ``merge`` probe time scaled with TOTAL capacity, not with the arrival
count. The engine carries a persistent ``particles.FreeSlotRing`` per
capacity group in its state: migration leavers and wall-absorbed particles
push their (already-packed, O(max_migration)) slot indices, arrivals pop
pre-claimed slots, and the scatter itself is **deferred into the next
step's ingest** — the pass that is about to stream the whole buffer through
the push anyway. The merge phase keeps only O(max_migration) ring
bookkeeping plus the carried-rho arrival deposit. In-flight arrivals live
in ``EngineState.pending`` and are counted by the step diagnostics, so
conservation is exact at every step boundary.

Monte-Carlo sources ride the same ring (this is what lets the paper's §3.3
ionization scenario and the SEE plasma-wall source run on the async
pipeline — no more legacy full-scan demotion):

* **ionization** runs per queue, between that queue's push and its
  migration exchange: ``collisions.ionize_packed`` draws events over the
  queue slice and packs at most ``EngineConfig.max_births / async_n`` of
  them (queue-sized scan only). The freed neutral slots feed the ring
  exactly like migration leavers; the electron/ion birth rows pop
  PRE-CLAIMED slots from their species' rings — claimed as a pair under a
  shared ``min(count_e, count_i)`` budget, so a birth either gets both
  slots or neither (never a half-born pair, never a leaked slot). Hits
  beyond the budget or the rings simply do not ionize this step and retry
  (``birth_overflow``, mirroring ``migration_overflow``).
* **wall emission (SEE)** consumes the absorbed rows of each queue's
  migration pack (already packed — no scan): yield-thinned secondaries
  claim slots from the target species' ring the same way
  (``emission_overflow`` counts ring-refused candidates).

Both kinds of birth rows join the migration arrivals in
``EngineState.pending`` and land at the next ingest, so the step
diagnostics (reduced over pending-flushed effective buffers) conserve
particle count and charge bitwise at every step boundary. With
``strategy='fused'`` the birth charge is deposited into the carried rho at
merge time (the same arrival-style correction migration uses), so the
carried-rho fast path now covers MC-source runs with the field solve on.

``EngineConfig.use_ring=False`` keeps the legacy full-capacity-scan merge
as an opt-in debug/parity mode: the SAME MC events (identical keys) are
injected through ``inject_masked`` scans instead — the conservation suite
pins the two paths against each other on identical seeds. The parity
holds while nothing drops: legacy mode retains the pre-PR-4 loss
semantics at the margins (a full buffer at merge time drops a birth whose
neutral was already killed, counted by ``merge_dropped``), whereas the
ring path refuses the kill up front — run the ring path outside of parity
tests.

Queue-adaptive rebalance: the interleaved split is only even while
occupancy is; absorption/ionization churn drifts the per-queue alive counts
apart (per-species ``queue_occ`` / ``queue_skew`` diagnostics expose this).
``EngineConfig.rebalance_every = K`` compacts each capacity group (alive
slots first, stable) every K steps under ``lax.cond`` and rebuilds the ring
from the compacted counts; ``rebalance_skew = T`` additionally triggers the
same compaction whenever a group's per-queue occupancy skew exceeds T at
ingest — MC births are the churn rebalancing exists for, so the trigger
follows the diagnostic instead of only a fixed period.

Migration overflow (fixed in PR 2, vs the seed's ``exchange_species``):
every boundary crosser used to be killed even when the fixed-size pack
truncated, silently losing particles and charge. Now only the crossers that
actually won a pack slot (and, per direction, a send-budget slot) leave;
the rest stay local — clamped just inside the slab so the next gather is
in-bounds — and retry next step, reported via ``migration_overflow``.

Carried charge (``strategy='fused'``): the in-pass deposit of each queue is
threaded through ``mover.push_stacked(rho_carry=...)``, corrected by
subtracting the leavers' edge deposits and adding the accepted arrivals'
and births' — so the next step's field solve never re-reads the full
particle arrays. Charge is conserved exactly.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import boundaries, collisions, diagnostics, mover
from repro.core.grid import (Grid1D, deposit_density, deposit_stacked,
                             deposit_flat)
from repro.core.particles import (FreeSlotRing, SpeciesBuffer, StackedSpecies,
                                  init_uniform, inject_at, inject_masked,
                                  kill, kill_packed, ring_claim,
                                  ring_from_counts, ring_init, ring_push,
                                  sort_by_cell, stack_species, take)
from repro.core.params import RuntimeParams, b_active
from repro.core.pic import PICConfig, PICState
from repro.core.pic import _carries_rho as pic_carries_rho
from repro.distributed import halo
from repro.obs import tracing

Array = jax.Array

# cumulative phase checkpoints for the perf probes (see perf.py): a step
# built with upto=<phase> executes the pipeline through that phase and
# returns, so consecutive differences give per-phase wall times. ``collide``
# (the per-queue binary-collision menu, between each queue's push and its
# migration exchange) split out of the old fused ``collide_diag`` tail when
# the collision substrate landed.
PHASES = ("ingest", "field", "push", "collide", "migrate", "merge", "full")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Decomposition + queue schedule of a global PICConfig.

    ``async_n`` is the paper's async(n): the number of migration/compute
    queues each domain's particles are split into. ``max_migration`` is the
    per-species/per-direction/per-step send budget for the whole domain,
    split evenly across queues; ``max_births`` is the analogous per-domain
    budget for ionization pair births. ``rebalance_every = K`` re-evens the
    queue split every K steps (0 disables) and ``rebalance_skew = T``
    triggers the same compaction whenever per-queue occupancy skew exceeds
    T (0 disables): each capacity group is compacted (alive first) and the
    free-slot ring rebuilt, so per-queue occupancy skew stays bounded under
    absorption/ionization churn. ``use_ring=False`` selects the legacy
    full-capacity-scan merge — a debug/parity mode only (the conservation
    suite pins it against the ring path on identical seeds).

    ``metrics=True`` adds the observability counters to the step
    diagnostics — per-species free-slot-ring occupancy (``ring_free``) and
    in-flight pending rows (``pending_rows``) for the ``repro.obs`` metrics
    stream. Diagnostics-only: the engine state is bitwise identical with
    the toggle on or off (pinned in ``tests/test_obs.py``).

    ``cell_order=True`` is BIT1-style per-cell ordering: every rebalance
    (periodic or skew-triggered) counting-sorts each capacity group by cell
    instead of merely compacting it — live rows grouped by cell, dead rows
    at the tail — and rebuilds the free-slot ring in the same pass. The
    interleaved queue split of a cell-sorted buffer stripes every cell
    evenly across the queues, so each queue's slice is both occupancy-even
    AND a uniform sample of every cell: the per-queue cell bin tables the
    collide phase builds stay balanced, within-cell pairing finds partners
    in every queue, and deposits/gathers walk the grid monotonically (the
    memory locality BIT1 gets from per-cell lists).
    """
    pic: PICConfig                       # cfg.nc == GLOBAL cell count
    axis_names: tuple[str, ...] = ("data",)
    async_n: int = 1
    max_migration: int = 2048            # per species/direction/step
    species_capacity_local: int | None = None  # default: global cap / D
    rebalance_every: int = 0             # 0 = never re-split periodically
    rebalance_skew: int = 0              # 0 = no skew-triggered re-split
    max_births: int = 2048               # ionization births per domain/step
    use_ring: bool = True                # False: legacy full-scan merge
    cell_order: bool = False             # rebalance counting-sorts by cell
    metrics: bool = False                # extra diag for the metrics stream

    def __post_init__(self):
        object.__setattr__(self, "axis_names", tuple(self.axis_names))
        if self.async_n < 1:
            raise ValueError(f"async_n must be >= 1, got {self.async_n}")
        if self.max_migration % self.async_n != 0:
            raise ValueError(
                f"async_n ({self.async_n}) must divide max_migration "
                f"({self.max_migration}) so every queue gets an equal "
                f"send budget")
        if (self.pic.ionization is not None
                and self.max_births % self.async_n != 0):
            raise ValueError(
                f"async_n ({self.async_n}) must divide max_births "
                f"({self.max_births}) so every queue gets an equal "
                f"birth budget")
        if self.rebalance_every < 0:
            raise ValueError(
                f"rebalance_every must be >= 0, got {self.rebalance_every}")
        if self.rebalance_skew < 0:
            raise ValueError(
                f"rebalance_skew must be >= 0, got {self.rebalance_skew}")

    def num_domains(self, mesh: Mesh) -> int:
        n = 1
        for a in self.axis_names:
            n *= mesh.shape[a]
        return n

    def local_nc(self, mesh: Mesh) -> int:
        d = self.num_domains(mesh)
        assert self.pic.nc % d == 0, (self.pic.nc, d)
        return self.pic.nc // d

    def local_cap(self, sc, mesh: Mesh) -> int:
        if self.species_capacity_local is not None:
            return self.species_capacity_local
        d = self.num_domains(mesh)
        assert sc.capacity % d == 0
        return sc.capacity // d

    @property
    def queue_migration(self) -> int:
        return self.max_migration // self.async_n

    @property
    def queue_births(self) -> int:
        assert self.max_births % self.async_n == 0  # enforced when it matters
        return self.max_births // self.async_n


@partial(jax.tree_util.register_dataclass,
         data_fields=("x", "v", "w", "alive", "dest"), meta_fields=())
@dataclasses.dataclass
class PendingArrivals:
    """Rows received/born this step, scattered at the NEXT step's ingest.

    Rows are the concatenated per-queue migration packs of one capacity
    group, followed by its MC birth blocks (ionization pairs, SEE
    secondaries); ``dest`` holds the pre-claimed dead slot of each accepted
    row (the local capacity as a drop sentinel otherwise). Because the
    slots are claimed from the free-slot ring, the eventual scatter is
    gather-free — and deferring it merges it into the pass that streams the
    whole buffer anyway. The step diagnostics count pending rows as
    resident particles, so conservation holds at every step boundary.
    """

    x: Array      # (S, M)
    v: Array      # (S, M, 3)
    w: Array      # (S, M)
    alive: Array  # (S, M) bool — accepted rows only
    dest: Array   # (S, M) int32 pre-claimed slot, cap = dropped


@partial(jax.tree_util.register_dataclass,
         data_fields=("pic", "rings", "pending"), meta_fields=())
@dataclasses.dataclass
class EngineState:
    """Engine state: the PIC state plus the async-merge bookkeeping.

    ``rings`` / ``pending`` hold one entry per capacity group (matching
    ``_capacity_groups`` order), each batched over the group's species axis.
    Both are empty tuples in the legacy full-scan mode
    (``EngineConfig.use_ring=False``).
    """

    pic: PICState
    rings: tuple[FreeSlotRing, ...]
    pending: tuple[PendingArrivals, ...]

    # back-compat accessors: call sites written against PICState keep working
    @property
    def species(self):
        return self.pic.species

    @property
    def key(self):
        return self.pic.key

    @property
    def step(self):
        return self.pic.step

    @property
    def rho(self):
        return self.pic.rho


def _carries_rho(ecfg: EngineConfig) -> bool:
    """The carried in-pass deposit is exact when every post-push charge
    change is folded back in — the single-domain step's rule, reused so the
    two paths can never diverge. MC births (ionization pairs, SEE
    secondaries) are deposited with the merge-phase arrival correction, and
    an ionized neutral must carry zero charge (enforced by the shared
    rule) so its post-deposit death needs none."""
    return pic_carries_rho(ecfg.pic)


def _see_pairs(cfg: PICConfig) -> tuple[tuple[int, int], ...]:
    """Active (primary, target) wall-emission pairs (absorbing walls only,
    matching the single-domain cycle's rule)."""
    if cfg.wall_emission and cfg.boundary == "absorb":
        return tuple(cfg.wall_emission)
    return ()


def _local_cap_d(ecfg: EngineConfig, sc, d: int) -> int:
    """``EngineConfig.local_cap`` for a domain count rather than a mesh —
    the elastic-restore path reasons about the *checkpointed* D, for which
    no mesh exists on this host."""
    if ecfg.species_capacity_local is not None:
        return ecfg.species_capacity_local
    assert sc.capacity % d == 0, (sc.capacity, d)
    return sc.capacity // d


def _capacity_groups_d(ecfg: EngineConfig, d: int) -> list[tuple[int, ...]]:
    by_cap: dict[int, list[int]] = {}
    for i, sc in enumerate(ecfg.pic.species):
        by_cap.setdefault(_local_cap_d(ecfg, sc, d), []).append(i)
    return [tuple(v) for v in by_cap.values()]


def _capacity_groups(ecfg: EngineConfig, mesh: Mesh) -> list[tuple[int, ...]]:
    """Species indices grouped by equal local capacity: each group is one
    StackedSpecies and one set of async queues."""
    return _capacity_groups_d(ecfg, ecfg.num_domains(mesh))


def _species_location(groups) -> dict[int, tuple[int, int]]:
    """species index -> (capacity group, row within the group's stack)."""
    return {i: (g, j)
            for g, idxs in enumerate(groups) for j, i in enumerate(idxs)}


def _group_pending_rows(ecfg: EngineConfig, groups) -> list[int]:
    """Static pending-row count per capacity group: 2 directions x the
    migration budget, plus the group's MC birth blocks (an ionization block
    per queue lands in the electron's and ion's group — one shared block
    when they stack together; an SEE block per queue per pair lands in the
    target's group)."""
    cfg = ecfg.pic
    rows = [2 * ecfg.max_migration] * len(groups)
    loc = _species_location(groups)
    if cfg.ionization is not None:
        _, ei, ii = cfg.ionization
        for g in {loc[ei][0], loc[ii][0]}:
            rows[g] += ecfg.max_births
    for _, t in _see_pairs(cfg):
        rows[loc[t][0]] += 2 * ecfg.max_migration
    return rows


# The queue views below never form a (cap / n, n) array: on TPU the minor
# dimension of an array is tiled to 128 lanes, so n = 2 there would pad the
# whole particle buffer 64-fold (tens of GB at 16 Mi slots per species).


def _split_queues(st: StackedSpecies, n: int) -> list[StackedSpecies]:
    """Interleaved queue views: slot c -> queue c % n (keeps a compacted
    live block evenly spread across queues), as strided slices."""
    if n == 1:
        return [st]
    return [jax.tree.map(lambda a: a[:, k::n], st) for k in range(n)]


def _merge_queues(queues: list, n: int):
    """Inverse of ``_split_queues`` (works on any matching pytrees): the
    queues laid end to end, then one gather back into slot order."""
    if n == 1:
        return queues[0]

    def mg(*xs):
        capq = xs[0].shape[1]
        c = jnp.arange(capq * n)
        return jnp.take(jnp.concatenate(xs, axis=1),
                        (c % n) * capq + c // n, axis=1)

    return jax.tree.map(mg, *queues)


def _queue_occupancy(alive: Array, n: int) -> Array:
    """(cap,) alive mask -> (n,) per-queue alive counts (slot c -> c % n)."""
    return jnp.stack([jnp.sum(alive[k::n].astype(jnp.int32))
                      for k in range(n)])


def _exchange_queue(q, l_local: float, m: int, boundary: str,
                    is_first: Array, is_last: Array):
    """Pack one queue's boundary crossers (vmapped over the species axis).

    Returns (kept, pack_l, pack_r, leaver_x, leaver_w, freed_idx, freed_ok,
    absorbed_l, absorbed_r, diag): ``pack_l``/``pack_r`` are the fixed-size
    send buffers (in the receiver's frame); ``leaver_x``/``leaver_w`` cover
    every particle that left — sent or wall-absorbed — at its raw post-push
    position, for the carried-rho subtraction; ``freed_idx``/``freed_ok``
    are the queue-local slot indices those leavers vacated (already packed,
    so the free-slot ring is fed without any additional scan);
    ``absorbed_l``/``absorbed_r`` mark the packed rows absorbed at the
    global left/right wall — the SEE source consumes them with no further
    scan. Crossers that exceed the pack or the per-direction budget stay
    local (clamped, retried next step) instead of being lost.
    """

    def pack_one(x, v, w, alive):
        buf = SpeciesBuffer(x=x, v=v, w=w, alive=alive)
        cap = buf.capacity
        go_l = alive & (x < 0.0)
        go_r = alive & (x >= l_local)
        leave = go_l | go_r
        # ONE full-capacity packing scan for both directions (a particle
        # crosses at most one boundary); per-direction work is on 2m only
        idx = jnp.nonzero(leave, size=2 * m, fill_value=cap)[0]
        packed = take(buf, idx)
        went_l = packed.alive & (packed.x < 0.0)
        went_r = packed.alive & (packed.x >= l_local)
        ok_l = went_l & (jnp.cumsum(went_l.astype(jnp.int32)) - 1 < m)
        ok_r = went_r & (jnp.cumsum(went_r.astype(jnp.int32)) - 1 < m)
        ok = ok_l | ok_r                 # packed AND inside the send budget
        # scatter the verdict back to slot space: only winners leave
        gone = jnp.zeros((cap,), bool).at[idx].set(ok, mode="drop")
        kept = kill(buf, gone)
        # overflow fix: losers stay alive, clamped just inside the slab so
        # the next field gather is in-bounds; they re-cross next step
        stay = leave & ~gone
        x_in = jnp.clip(x, 0.0, jnp.nextafter(
            jnp.asarray(l_local, x.dtype), jnp.asarray(0.0, x.dtype)))
        kept = dataclasses.replace(kept, x=jnp.where(stay, x_in, kept.x))

        if boundary == "absorb":         # global walls absorb at edge domains
            abs_l = ok_l & is_first
            abs_r = ok_r & is_last
        else:                            # global periodic: the ring wraps
            abs_l = jnp.zeros_like(ok_l)
            abs_r = jnp.zeros_like(ok_r)
        absorb = abs_l | abs_r
        send_l = ok_l & ~absorb
        send_r = ok_r & ~absorb
        idx_l = jnp.nonzero(send_l, size=m, fill_value=2 * m)[0]
        idx_r = jnp.nonzero(send_r, size=m, fill_value=2 * m)[0]
        pack_l = take(packed, idx_l)
        pack_r = take(packed, idx_r)
        # shift into the receiver's local frame
        pack_l = dataclasses.replace(pack_l, x=pack_l.x + l_local)
        pack_r = dataclasses.replace(pack_r, x=pack_r.x - l_local)
        diag = {
            "migrated_left": jnp.sum(send_l.astype(jnp.int32)),
            "migrated_right": jnp.sum(send_r.astype(jnp.int32)),
            "migration_overflow": jnp.sum(stay.astype(jnp.int32)),
            "wall_absorbed": jnp.sum(absorb.astype(jnp.int32)),
        }
        return (kept, pack_l, pack_r, packed.x, packed.w * ok, idx, ok,
                abs_l, abs_r, diag)

    return jax.vmap(pack_one)(q.x, q.v, q.w, q.alive)


def _inject_rows(full: SpeciesBuffer, cand: SpeciesBuffer):
    """vmapped full-scan inject of (S, ncand) candidates into (S, cap)
    buffers — the legacy merge used in the opt-in parity mode
    (``use_ring=False``)."""

    def one(bx, bv, bw, ba, cx, cv, cw, ca):
        return inject_masked(SpeciesBuffer(x=bx, v=bv, w=bw, alive=ba),
                             cx, cv, cw, ca)

    return jax.vmap(one)(full.x, full.v, full.w, full.alive,
                         cand.x, cand.v, cand.w, cand.alive)


def _flush_pending(st: StackedSpecies, p: PendingArrivals) -> StackedSpecies:
    """Scatter pre-claimed arrivals into their ring-assigned slots, one
    species at a time. The slots were dead when claimed and nothing re-fills
    slots between merge and ingest, so this is exact. (A scatter vmapped
    over the species axis computes the same, but at 16 Mi slots per species
    it took the TPU compiler minutes where this loop takes seconds.)"""
    return stack_species([
        inject_at(SpeciesBuffer(x=st.x[j], v=st.v[j], w=st.w[j],
                                alive=st.alive[j]),
                  p.dest[j], p.x[j], p.v[j], p.w[j], p.alive[j])
        for j in range(st.num_species)])


def _empty_pending(s: int, m: int, cap: int, dtype) -> PendingArrivals:
    return PendingArrivals(
        x=jnp.zeros((s, m), dtype), v=jnp.zeros((s, m, 3), dtype),
        w=jnp.zeros((s, m), dtype), alive=jnp.zeros((s, m), bool),
        dest=jnp.full((s, m), cap, jnp.int32))


def _birth_block(s: int, nb: int, cap: int, dtype,
                 rows: dict) -> PendingArrivals:
    """One (S, nb) pending block whose live rows are MC births.

    ``rows`` maps a species row j to its (x, v, w, ok, dest) candidate
    arrays — an ionization block carries the electron AND ion rows of the
    same events when the two species share a capacity group; every other
    row stays dead. ``dest=None`` (legacy full-scan mode) leaves the drop
    sentinel, which ``_inject_rows`` never reads."""
    bx = jnp.zeros((s, nb), dtype)
    bv = jnp.zeros((s, nb, 3), dtype)
    bw = jnp.zeros((s, nb), dtype)
    ba = jnp.zeros((s, nb), bool)
    bd = jnp.full((s, nb), cap, jnp.int32)
    for j, (x, v, w, ok, dest) in rows.items():
        ok = ok.astype(bool)
        bx = bx.at[j].set(x)
        bv = bv.at[j].set(v)
        bw = bw.at[j].set(w * ok)
        ba = ba.at[j].set(ok)
        if dest is not None:
            bd = bd.at[j].set(dest.astype(jnp.int32))
    return PendingArrivals(x=bx, v=bv, w=bw, alive=ba, dest=bd)


def _claim_rows(ring: FreeSlotRing, want_rows: dict, cap: int,
                budget: Array | None = None):
    """Claim slots from a group-batched ring for the given species rows.

    ``want_rows`` maps row j -> (M,) want mask; other rows claim nothing.
    ``budget`` (scalar) caps every row's grants — paired ionization claims
    pass ``min(count_e, count_i)`` so both rows grant the same set.
    Returns (ring, dest (S, M), ok (S, M))."""
    s = ring.count.shape[0]
    m = next(iter(want_rows.values())).shape[0]
    want = jnp.zeros((s, m), bool)
    for j, wv in want_rows.items():
        want = want.at[j].set(wv.astype(bool))
    if budget is None:
        return jax.vmap(lambda rg, wv: ring_claim(rg, wv, cap))(ring, want)
    bud = jnp.broadcast_to(budget, (s,))
    return jax.vmap(lambda rg, wv, bd: ring_claim(rg, wv, cap, bd))(
        ring, want, bud)


def _push_rows(ring: FreeSlotRing, idx_rows: dict, m: int) -> FreeSlotRing:
    """Push freed slots into a group-batched ring for the given species
    rows. ``idx_rows`` maps row j -> (idx (M,), ok (M,)); other rows push
    nothing."""
    s = ring.count.shape[0]
    idx = jnp.zeros((s, m), jnp.int32)
    okm = jnp.zeros((s, m), bool)
    for j, (iv, ov) in idx_rows.items():
        idx = idx.at[j].set(iv.astype(jnp.int32))
        okm = okm.at[j].set(ov.astype(bool))
    return jax.vmap(ring_push)(ring, idx, okm)


def _compact_group(st: StackedSpecies) -> tuple[StackedSpecies, Array]:
    """Stable per-species compaction (alive first): the interleaved queue
    split of the result is occupancy-even by construction. Returns the
    compacted group and its per-species alive counts."""

    def one(x, v, w, alive):
        order = jnp.argsort(~alive, stable=True)
        return x[order], v[order], w[order], alive[order]

    x, v, w, alive = jax.vmap(one)(st.x, st.v, st.w, st.alive)
    out = StackedSpecies(x=x, v=v, w=w, alive=alive)
    return out, out.counts()


def _cellsort_group(st: StackedSpecies, dx: float,
                    nc: int) -> tuple[StackedSpecies, Array]:
    """Per-species counting-sort by cell (``particles.sort_by_cell`` vmapped
    over the group): live rows grouped by cell, dead rows at the tail —
    which is also a valid compaction, so the ring rebuild
    (``ring_from_counts``) and the occupancy-even queue split carry over
    unchanged. The ``cell_order=True`` rebalance mode."""

    def one(x, v, w, alive):
        b = sort_by_cell(SpeciesBuffer(x=x, v=v, w=w, alive=alive), dx, nc)
        return b.x, b.v, b.w, b.alive

    x, v, w, alive = jax.vmap(one)(st.x, st.v, st.w, st.alive)
    out = StackedSpecies(x=x, v=v, w=w, alive=alive)
    return out, out.counts()


def _state_specs(ecfg: EngineConfig, mesh: Mesh) -> EngineState:
    part = P(ecfg.axis_names)
    carried = _carries_rho(ecfg)
    pic = PICState(
        species=tuple(
            SpeciesBuffer(x=part, v=part, w=part, alive=part)
            for _ in ecfg.pic.species),
        key=part, step=P(), rho=part if carried else None)
    if not ecfg.use_ring:
        return EngineState(pic=pic, rings=(), pending=())
    groups = _capacity_groups(ecfg, mesh)
    rings = tuple(FreeSlotRing(slots=part, head=part, count=part)
                  for _ in groups)
    pending = tuple(
        PendingArrivals(x=part, v=part, w=part, alive=part, dest=part)
        for _ in groups)
    return EngineState(pic=pic, rings=rings, pending=pending)


def _lift_tree(tree):
    """Re-attach the leading sharded (1, ...) device axis to every leaf."""
    return jax.tree.map(lambda a: a[None], tree)


def _lift(species, key, step, rho) -> PICState:
    return PICState(
        species=tuple(_lift_tree(b) for b in species),
        key=key[None], step=step, rho=rho)


def make_engine_step(ecfg: EngineConfig, mesh: Mesh, *, upto: str = "full",
                     donate: bool = True, with_params: bool = False):
    """Build the shard_map'd async(n) PIC step.

    ``upto='full'`` (default) returns the production step: jit-compiled,
    state-donating, ``state -> (state, diag)``. Earlier values of ``upto``
    build the perf probes (see ``PHASES``): the pipeline runs through that
    phase and returns ``(state, aux)`` undonated, so cumulative differencing
    yields per-phase times without instrumenting the hot path.

    ``with_params=True`` returns ``(state, params) -> (state, diag)`` taking
    a ``RuntimeParams`` pytree (replicated across domains) for the runtime
    scalars — dt, source coefficients, collision rates, b — so every
    parameter point of a sweep runs through ONE compiled step. Identical
    values are bit-identical to the static build (see ``core/params.py``).
    """
    if upto not in PHASES:
        raise ValueError(f"upto must be one of {PHASES}, got {upto!r}")
    cfg = ecfg.pic
    ncl = ecfg.local_nc(mesh)
    grid_local = Grid1D(nc=ncl, dx=cfg.dx)
    l_local = ncl * cfg.dx
    d = ecfg.num_domains(mesh)
    n_q = ecfg.async_n
    m_q = ecfg.queue_migration
    b_q = ecfg.queue_births if cfg.ionization is not None else 0
    carried = _carries_rho(ecfg)
    use_ring = ecfg.use_ring
    reb_k = ecfg.rebalance_every
    skew_k = ecfg.rebalance_skew
    groups = _capacity_groups(ecfg, mesh)
    loc = _species_location(groups)
    prows = _group_pending_rows(ecfg, groups)
    group_caps = [ecfg.local_cap(cfg.species[idxs[0]], mesh)
                  for idxs in groups]
    ion = cfg.ionization
    see_pairs = _see_pairs(cfg)
    has_mc = ion is not None or bool(see_pairs)
    coll = tuple(cfg.collisions)
    for i, sc in enumerate(cfg.species):
        cap_l = ecfg.local_cap(sc, mesh)
        if cap_l % n_q != 0:
            raise ValueError(
                f"async_n ({n_q}) must divide the local capacity ({cap_l}) "
                f"of species {sc.name!r}")
    for cc in coll:
        # a queue is one capacity group's slice: binary partners must ride
        # the same queue, so every species of one menu entry must share a
        # capacity group (single-domain runs have no such constraint)
        parts = collisions.involved_species([cc])
        if len({loc[i][0] for i in parts}) != 1:
            names = [cfg.species[i].name for i in parts]
            raise ValueError(
                f"collision {cc.kind!r} pairs species {names} across "
                f"capacity groups; give them equal capacities to run on "
                f"the engine")
    axis_names = ecfg.axis_names

    def local_step(estate: EngineState, rp: RuntimeParams | None = None):
        state = estate.pic
        species = [jax.tree.map(lambda a: a[0], b) for b in state.species]
        rings = [jax.tree.map(lambda a: a[0], r) for r in estate.rings]
        pend_in = [jax.tree.map(lambda a: a[0], p) for p in estate.pending]
        key = state.key[0]
        r = halo.rank(axis_names)
        is_first = r == 0
        is_last = r == d - 1

        def group_meta(idxs):
            scs = [cfg.species[i] for i in idxs]
            dtype = species[idxs[0]].x.dtype
            qm = jnp.asarray([sc.charge / sc.mass for sc in scs], dtype)
            dts = (jnp.asarray([cfg.dt * sc.stride for sc in scs], dtype)
                   if rp is None
                   else rp.dts[jnp.asarray(list(idxs))].astype(dtype))
            charges = jnp.asarray([sc.charge for sc in scs], dtype)
            return scs, qm, dts, charges

        def write_back(idxs, full):
            for j, i in enumerate(idxs):
                species[i] = SpeciesBuffer(
                    x=full.x[j], v=full.v[j], w=full.w[j],
                    alive=full.alive[j])

        def pack_state(rho, pend_out):
            return EngineState(
                pic=_lift(species, key, state.step + 1, rho),
                rings=tuple(_lift_tree(rg) for rg in rings),
                pending=tuple(_lift_tree(p) for p in pend_out))

        # ---- ingest: land last step's arrivals + births in their
        #      pre-claimed slots (the scatter deferred out of the merge
        #      phase), then compact + re-split the queues — every
        #      rebalance_every steps, or whenever the post-flush per-queue
        #      occupancy skew exceeds rebalance_skew ----
        rebalance_periodic = None
        if reb_k > 0:
            rebalance_periodic = (state.step > 0) & (state.step % reb_k == 0)
        with tracing.phase_scope("engine/ingest"):
            for g, idxs in enumerate(groups):
                cap_g = group_caps[g]
                if not (use_ring or reb_k > 0 or skew_k > 0):
                    continue
                st = stack_species([species[i] for i in idxs])
                if use_ring:
                    st = _flush_pending(st, pend_in[g])
                reb_g = rebalance_periodic
                if skew_k > 0:
                    occ = jax.vmap(
                        lambda a: _queue_occupancy(a, n_q))(st.alive)
                    skew = jnp.max(jnp.max(occ, axis=1)
                                   - jnp.min(occ, axis=1))
                    trig = (state.step > 0) & (skew > skew_k)
                    reb_g = trig if reb_g is None else (reb_g | trig)
                if reb_g is not None:
                    # cell_order swaps the plain compaction for the
                    # BIT1-style counting sort by cell (dead rows still at
                    # the tail, so the ring rebuild is the same closed form)
                    sort_group = (
                        (lambda s: _cellsort_group(s, cfg.dx, ncl))
                        if ecfg.cell_order else _compact_group)
                    if use_ring:
                        def reb(op):
                            new, counts = sort_group(op[0])
                            return new, jax.vmap(
                                lambda c: ring_from_counts(c, cap_g))(counts)

                        st, rings[g] = jax.lax.cond(
                            reb_g, reb, lambda op: op, (st, rings[g]))
                    else:
                        st = jax.lax.cond(
                            reb_g, lambda s: sort_group(s)[0],
                            lambda s: s, st)
                write_back(idxs, st)
        empty_pend = [
            _empty_pending(len(idxs), prows[g], group_caps[g],
                           species[idxs[0]].x.dtype)
            for g, idxs in enumerate(groups)] if use_ring else []
        if upto == "ingest":
            aux = sum(jnp.sum(b.alive.astype(jnp.float32))
                      for b in species).reshape(1)
            return pack_state(state.rho, empty_pend), aux

        # ---- field phase: halo exchange, never a full-rho all_gather ----
        with tracing.phase_scope("engine/field"):
            if not cfg.field_solve:
                e = jnp.zeros((ncl + 1,), jnp.float32)
            else:
                if carried and state.rho is not None:
                    rho_local = state.rho[0]
                else:
                    rho_local = jnp.zeros((ncl + 1,), jnp.float32)
                    for idxs in groups:
                        _, _, _, charges = group_meta(idxs)
                        st = stack_species([species[i] for i in idxs])
                        rho_local = rho_local + deposit_stacked(
                            grid_local, st.x, st.w, st.alive, charges)
                e = halo.field_phase(
                    rho_local, dx=cfg.dx, eps0=cfg.eps0,
                    smoothing_passes=cfg.smoothing_passes,
                    axis_names=axis_names, mesh=mesh, is_first=is_first,
                    is_last=is_last)
        if upto == "field":
            return pack_state(state.rho, empty_pend), e[None]

        diag: dict = {}

        def dacc(name, k, v):
            key_ = f"{name}/{k}" if name else k
            diag[key_] = diag.get(key_, 0) + v

        rho_acc = jnp.zeros((ncl + 1,), jnp.float32) if carried else None

        # ---- MC source inputs: one electron-density deposit (halo-summed
        #      at the shared edge nodes) and per-queue event keys, derived
        #      identically in ring and legacy modes so the two paths draw
        #      the same physics from the same seed ----
        ne_local = None
        iparams = eparams = None
        ion_keys = see_keys = None
        with tracing.phase_scope("engine/sources"):
            if ion is not None:
                iparams = collisions.IonizationParams(
                    rate=(cfg.ionization_rate if rp is None
                          else rp.ionization_rate),
                    vth_electron=cfg.ionization_vth_e)
                ne_local = halo.halo_sum(
                    deposit_density(grid_local, species[ion[1]]),
                    axis_names, mesh, is_first, is_last)
            if see_pairs:
                eparams = boundaries.EmissionParams(
                    yield_=(cfg.emission_yield if rp is None
                            else rp.emission_yield),
                    vth_emit=cfg.emission_vth,
                    weight=cfg.emission_weight)
            if has_mc:
                key, k_mc = jax.random.split(key)
                k_mc = jax.random.fold_in(k_mc, r)
                k_ion, k_see = jax.random.split(k_mc)
                ion_keys = jax.random.split(k_ion, n_q)
                if see_pairs:
                    see_keys = jax.random.split(
                        k_see, len(see_pairs) * n_q).reshape(
                        (len(see_pairs), n_q, -1))

        # ---- collide inputs: per-cell rate densities from the full local
        #      buffers (cells are wholly domain-owned — no halo needed) and
        #      per-queue event keys. A queue pairs within its own slice but
        #      collides at the full-domain rate ----
        coll_dens = None
        coll_keys = None
        if coll:
            with tracing.phase_scope("engine/collide_setup"):
                coll_dens = {
                    i: collisions.cell_density(grid_local, species[i])
                    for i in collisions.density_species(coll)}
                key, k_coll = jax.random.split(key)
                k_coll = jax.random.fold_in(k_coll, r)
                coll_keys = jax.random.split(k_coll, n_q)

        # ---- async(n) pipeline: push queue k, run its MC sources, issue
        #      its migration collective, then push queue k+1 while k's
        #      permute flies ----
        staged = []
        birth_blocks: list[list] = [[] for _ in groups]
        for g, idxs in enumerate(groups):
            scs, qm, dts, charges = group_meta(idxs)
            strides = [sc.stride for sc in scs]
            dtype = species[idxs[0]].x.dtype
            st = stack_species([species[i] for i in idxs])
            kept_qs, pending_packs = [], []
            for k_q, q in enumerate(_split_queues(st, n_q)):
                with tracing.phase_scope(f"engine/push/q{k_q}"):
                    out, hl, hr, pdiag, rho_push = mover.push_stacked(
                        q, e, grid_local, qm, dts,
                        b=(rp.b_field.astype(dtype)
                           if rp is not None and b_active(cfg)
                           else cfg.b_field),
                        boundary="open", gather_mode=cfg.gather_mode,
                        charges=charges if carried else None,
                        rho_carry=rho_acc if carried else None)
                    if any(s > 1 for s in strides):
                        # sub-cycling: heavy species push every `stride`
                        # steps
                        do = jnp.mod(state.step, jnp.asarray(strides)) == 0
                        sel = lambda new, old: jnp.where(
                            do.reshape((-1,) + (1,) * (new.ndim - 1)),
                            new, old)
                        out = jax.tree.map(sel, out, q)
                        pdiag = {k: jnp.where(do, v, jnp.zeros_like(v))
                                 for k, v in pdiag.items()}
                    for j, sc in enumerate(scs):
                        for k, v in pdiag.items():
                            dacc(sc.name, k, v[j])
                if upto == "push":
                    if carried:
                        rho_acc = rho_push      # keep the in-pass deposit
                    kept_qs.append(out)         # live in the probe output
                    continue

                # ---- binary collisions on this queue (before the MC
                #      sources and the exchange): the menu runs on the
                #      queue's own slices through the SAME apply_menu the
                #      single-domain cycle uses. Collisions touch only
                #      velocities — no alive-mask change, hence no ring
                #      traffic and no carried-rho correction ----
                g_pairs = [(k_m, cc) for k_m, cc in enumerate(coll)
                           if loc[cc.species][0] == g]
                g_coll = [cc for _, cc in g_pairs]
                if g_coll:
                    with tracing.phase_scope(f"engine/collide/q{k_q}"):
                        rows_c = collisions.involved_species(g_coll)
                        cbufs = {i: SpeciesBuffer(
                            x=out.x[idxs.index(i)], v=out.v[idxs.index(i)],
                            w=out.w[idxs.index(i)],
                            alive=out.alive[idxs.index(i)])
                            for i in rows_c}
                        cbufs, cdiag = collisions.apply_menu(
                            jax.random.fold_in(coll_keys[k_q], g), cbufs,
                            g_coll, coll_dens, grid_local,
                            cfg.dt if rp is None else rp.dt,
                            cfg.collide_kernel,
                            rates=(None if rp is None else tuple(
                                rp.collision_rates[k_m]
                                for k_m, _ in g_pairs)))
                        for i, cb in cbufs.items():
                            j = idxs.index(i)
                            out = StackedSpecies(
                                x=out.x, v=out.v.at[j].set(cb.v), w=out.w,
                                alive=out.alive)
                        for ck, cv in cdiag.items():
                            dacc(None, ck, cv)
                if upto == "collide":
                    if carried:
                        rho_acc = rho_push
                    kept_qs.append(out)
                    continue

                # ---- MC ionization on this queue (before the exchange, so
                #      ionized neutrals are never packed as crossers) ----
                if ion is not None and ion[0] in idxs:
                    with tracing.phase_scope(f"engine/ionize/q{k_q}"):
                        ni, ei, ii = ion
                        jn = idxs.index(ni)
                        qn = SpeciesBuffer(x=out.x[jn], v=out.v[jn],
                                           w=out.w[jn], alive=out.alive[jn])
                        pack = collisions.ionize_packed(
                            ion_keys[k_q], qn, grid_local, iparams,
                            cfg.dt if rp is None else rp.dt,
                            ne_local, b_q)
                        (ge, je), (gi, ji) = loc[ei], loc[ii]
                        if use_ring:
                            # pre-claim one electron + one ion slot per
                            # birth under the shared min-count budget: a
                            # birth gets both slots or neither (no half
                            # pairs, no leaks)
                            if ge == gi:
                                avail = jnp.minimum(rings[ge].count[je],
                                                    rings[ge].count[ji])
                                rings[ge], dest, okm = _claim_rows(
                                    rings[ge], {je: pack.ok, ji: pack.ok},
                                    group_caps[ge], avail)
                                allowed = okm[je]
                                dest_e, dest_i = dest[je], dest[ji]
                            else:
                                avail = jnp.minimum(rings[ge].count[je],
                                                    rings[gi].count[ji])
                                rings[ge], de, oe = _claim_rows(
                                    rings[ge], {je: pack.ok},
                                    group_caps[ge], avail)
                                rings[gi], di, _ = _claim_rows(
                                    rings[gi], {ji: pack.ok},
                                    group_caps[gi], avail)
                                allowed = oe[je]
                                dest_e, dest_i = de[je], di[ji]
                            # freed neutral slots feed the ring like
                            # leavers (queue slot j -> global slot
                            # j * n_q + k_q)
                            rings[g] = _push_rows(
                                rings[g],
                                {jn: (pack.slot * n_q + k_q, allowed)}, b_q)
                        else:
                            allowed = pack.ok
                            dest_e = dest_i = None
                        killed = kill_packed(qn, pack.slot, allowed)
                        out = StackedSpecies(
                            x=out.x.at[jn].set(killed.x),
                            v=out.v.at[jn].set(killed.v),
                            w=out.w.at[jn].set(killed.w),
                            alive=out.alive.at[jn].set(killed.alive))
                        e_row = (pack.x, pack.v_electron, pack.w, allowed,
                                 dest_e)
                        i_row = (pack.x, pack.v_ion, pack.w, allowed,
                                 dest_i)
                        if ge == gi:
                            birth_blocks[ge].append(_birth_block(
                                len(groups[ge]), b_q, group_caps[ge],
                                dtype, {je: e_row, ji: i_row}))
                        else:
                            birth_blocks[ge].append(_birth_block(
                                len(groups[ge]), b_q, group_caps[ge],
                                dtype, {je: e_row}))
                            birth_blocks[gi].append(_birth_block(
                                len(groups[gi]), b_q, group_caps[gi],
                                dtype, {ji: i_row}))
                        n_born = jnp.sum(allowed.astype(jnp.int32))
                        dacc(None, "n_ionized", n_born)
                        dacc(None, "birth_overflow", pack.n_events - n_born)

                with tracing.phase_scope(f"engine/migrate/q{k_q}"):
                    (kept, pack_l, pack_r, lv_x, lv_w, free_idx, free_ok,
                     abs_l, abs_r, dmig) = _exchange_queue(
                        out, l_local, m_q, cfg.boundary, is_first, is_last)
                    if carried:
                        # leavers were deposited at their raw (edge-clipped)
                        # positions by the in-pass deposit; take them back
                        # out
                        rho_acc = rho_push - deposit_flat(
                            grid_local, lv_x, charges[:, None] * lv_w)
                    if use_ring:
                        # leaver slots are free from here on: feed the ring
                        # from the already-packed indices (queue slot j ->
                        # global slot j * n_q + k_q), no extra scan
                        rings[g] = jax.vmap(ring_push)(
                            rings[g], free_idx * n_q + k_q, free_ok)

                    # ---- SEE: yield-thinned secondaries off this queue's
                    #      absorbed rows (already packed by the exchange) --
                    for pi, (p, t) in enumerate(see_pairs):
                        if p not in idxs:
                            continue
                        with tracing.phase_scope(f"engine/see/q{k_q}"):
                            jp = idxs.index(p)
                            emit, ex, ev, ew = \
                                boundaries.emission_candidates(
                                    see_keys[pi, k_q], abs_l[jp], abs_r[jp],
                                    eparams, l_local, dtype)
                            gt, jt = loc[t]
                            if use_ring:
                                rings[gt], dstm, okm = _claim_rows(
                                    rings[gt], {jt: emit}, group_caps[gt])
                                ok_t, dest_t = okm[jt], dstm[jt]
                            else:
                                ok_t, dest_t = emit, None
                            birth_blocks[gt].append(_birth_block(
                                len(groups[gt]), 2 * m_q, group_caps[gt],
                                dtype, {jt: (ex, ev, ew, ok_t, dest_t)}))
                            n_emit = jnp.sum(ok_t.astype(jnp.int32))
                            dacc(cfg.species[t].name, "emitted", n_emit)
                            dacc(cfg.species[t].name, "emission_overflow",
                                 jnp.sum((emit & ~ok_t).astype(jnp.int32)))

                    recv_r = halo.ppermute_tree(pack_l, axis_names, -1,
                                                mesh)
                    recv_l = halo.ppermute_tree(pack_r, axis_names, +1,
                                                mesh)
                    kept_qs.append(StackedSpecies(
                        x=kept.x, v=kept.v, w=kept.w, alive=kept.alive))
                    pending_packs.append((recv_l, recv_r))
                    for j, sc in enumerate(scs):
                        for k, v in dmig.items():
                            dacc(sc.name, k, v[j])
            staged.append((idxs, charges, kept_qs, pending_packs))

        if upto in ("push", "collide", "migrate"):
            aux = e
            for idxs, _, kept_qs, pending_packs in staged:
                write_back(idxs, _merge_queues(kept_qs, n_q))
                # keep the received packs live in the probe output so the
                # migration collectives are not dead-code-eliminated
                for recv in pending_packs:
                    for leaf in jax.tree.leaves(recv):
                        aux = aux + jnp.sum(leaf.astype(jnp.float32))
            rho_out = rho_acc[None] if carried else state.rho
            return pack_state(rho_out, empty_pend), aux[None]

        # ---- deferred merge: every queue's collective has been issued.
        #      Ring path: claim a dead slot per arrival from the free-slot
        #      ring (O(max_migration)), append the queues' birth blocks
        #      (slots already claimed), and carry the rows as pending — the
        #      scatter happens at the NEXT step's ingest. Legacy path
        #      (use_ring=False): one full-capacity free-slot scan per
        #      species over arrivals AND births, scattered immediately ----
        pend_out = list(empty_pend)
        with tracing.phase_scope("engine/merge"):
            for g, (idxs, charges, kept_qs,
                    pending_packs) in enumerate(staged):
                scs = [cfg.species[i] for i in idxs]
                cap_g = group_caps[g]
                full = _merge_queues(kept_qs, n_q)
                packs = [p for pair in pending_packs for p in pair]
                cand = jax.tree.map(
                    lambda *xs: jnp.concatenate(xs, axis=1), *packs)
                if use_ring:
                    rings[g], dest, accepted = jax.vmap(
                        lambda rg, wnt: ring_claim(rg, wnt, cap_g))(
                        rings[g], cand.alive)
                    blocks = [PendingArrivals(
                        x=cand.x, v=cand.v, w=cand.w * accepted,
                        alive=cand.alive & accepted, dest=dest)]
                    blocks += birth_blocks[g]
                    pend_g = blocks[0] if len(blocks) == 1 else jax.tree.map(
                        lambda *xs: jnp.concatenate(xs, axis=1), *blocks)
                    pend_out[g] = pend_g
                    dropped = jnp.sum(
                        (cand.alive & ~accepted).astype(jnp.int32), axis=1)
                    write_back(idxs, full)
                    if carried:
                        rho_acc = rho_acc + deposit_flat(
                            grid_local, pend_g.x,
                            charges[:, None] * pend_g.w * pend_g.alive)
                else:
                    extra = [SpeciesBuffer(x=b.x, v=b.v, w=b.w,
                                           alive=b.alive)
                             for b in birth_blocks[g]]
                    cand_all = cand if not extra else jax.tree.map(
                        lambda *xs: jnp.concatenate(xs, axis=1), cand,
                        *extra)
                    merged, dropped, accepted = _inject_rows(full, cand_all)
                    write_back(idxs, merged)
                    if carried:
                        rho_acc = rho_acc + deposit_flat(
                            grid_local, cand_all.x,
                            charges[:, None] * cand_all.w * accepted)
                for j, sc in enumerate(scs):
                    dacc(sc.name, "merge_dropped", dropped[j])
        rho_out = rho_acc[None] if carried else state.rho
        if upto == "merge":
            return pack_state(rho_out, pend_out), e[None]

        # ---- global diagnostics (psum over domains; skew uses pmax) ----
        # in-flight arrivals and births are resident particles: reduce over
        # an EFFECTIVE buffer with pending scattered into its (dead, w == 0)
        # pre-claimed slots. The per-slot writes land on exact zeros, so the
        # reductions match the post-ingest buffer bitwise — a separate
        # pending sum term would flip the charge total by an ulp and break
        # the engine's exact cross-D conservation contract.
        with tracing.phase_scope("engine/diag"):
            eff = list(species)
            if use_ring:
                for g, idxs in enumerate(groups):
                    st = _flush_pending(
                        stack_species([species[i] for i in idxs]),
                        pend_out[g])
                    for j, i in enumerate(idxs):
                        eff[i] = SpeciesBuffer(
                            x=st.x[j], v=st.v[j], w=st.w[j],
                            alive=st.alive[j])
            for sc, buf in zip(cfg.species, eff):
                diag[f"{sc.name}/count"] = buf.count()
                diag[f"{sc.name}/ke"] = diagnostics.kinetic_energy(
                    buf, sc.mass)
                diag[f"{sc.name}/charge"] = diagnostics.total_charge(
                    buf, sc.charge)
                occ = _queue_occupancy(buf.alive, n_q)
                diag[f"{sc.name}/queue_occ"] = occ
                diag[f"{sc.name}/queue_skew"] = jnp.max(occ) - jnp.min(occ)
            if ecfg.metrics and use_ring:
                # observability extras (diagnostics-only — the state math
                # is untouched, so metrics on/off stays bitwise identical):
                # free-slot-ring occupancy and in-flight pending rows, the
                # quantities the auto-tuner's budget decisions read
                for i, sc in enumerate(cfg.species):
                    g, j = loc[i]
                    diag[f"{sc.name}/ring_free"] = rings[g].count[j]
                    diag[f"{sc.name}/pending_rows"] = jnp.sum(
                        pend_out[g].alive[j].astype(jnp.int32))
            diag = {k: (jax.lax.pmax(v, axis_names)
                        if k.endswith("/queue_skew")
                        else jax.lax.psum(v, axis_names))
                    for k, v in diag.items()}

        return pack_state(rho_out, pend_out), diag

    specs_state = _state_specs(ecfg, mesh)
    out_specs = ((specs_state, P()) if upto == "full"
                 else (specs_state, P(axis_names)))
    donate_kw = {"donate_argnums": (0,)} if (donate and upto == "full") else {}
    if with_params:
        # runtime params ride replicated (P() on every leaf): each domain
        # reads the same scalars, nothing is ever sharded or donated
        rp_specs = jax.tree.map(lambda _: P(),
                                RuntimeParams.from_config(cfg))
        step = jax.shard_map(
            local_step, mesh=mesh, in_specs=(specs_state, rp_specs),
            out_specs=out_specs, check_vma=False)
        return jax.jit(step, **donate_kw)
    step = jax.shard_map(
        lambda estate: local_step(estate), mesh=mesh,
        in_specs=(specs_state,), out_specs=out_specs,
        check_vma=False)
    return jax.jit(step, **donate_kw)


def _engine_extras(ecfg: EngineConfig, mesh: Mesh, bufs):
    """Rings + empty pending for per-domain species buffers (init-time only:
    the one full free-slot scan the ring design allows)."""
    groups = _capacity_groups(ecfg, mesh)
    prows = _group_pending_rows(ecfg, groups)
    rings, pending = [], []
    for g, idxs in enumerate(groups):
        # one ring per species, stacked (not vmapped: see _flush_pending)
        rings.append(jax.tree.map(lambda *a: jnp.stack(a),
                                  *[ring_init(bufs[i].alive) for i in idxs]))
        pending.append(_empty_pending(
            len(idxs), prows[g], bufs[idxs[0]].capacity,
            bufs[idxs[0]].x.dtype))
    return tuple(rings), tuple(pending)


def attach_engine_state(ecfg: EngineConfig, mesh: Mesh,
                        state: PICState) -> EngineState:
    """Wrap an externally built (device-lifted) PICState into an EngineState:
    free-slot rings rebuilt from the alive masks, no in-flight arrivals.

    Use this to feed the engine a state produced by ``pic.init_state`` (via
    the usual ``[None]`` lift) or by an older checkpoint.
    """
    if not ecfg.use_ring:
        return EngineState(pic=state, rings=(), pending=())

    def local(st: PICState) -> EngineState:
        bufs = [jax.tree.map(lambda a: a[0], b) for b in st.species]
        rings, pending = _engine_extras(ecfg, mesh, bufs)
        return EngineState(
            pic=st, rings=tuple(_lift_tree(rg) for rg in rings),
            pending=tuple(_lift_tree(p) for p in pending))

    specs = _state_specs(ecfg, mesh)
    f = jax.shard_map(local, mesh=mesh, in_specs=(specs.pic,),
                      out_specs=specs, check_vma=False)
    return jax.jit(f)(state)


def retarget_state(old: EngineConfig, new: EngineConfig, mesh: Mesh,
                   state: EngineState) -> EngineState:
    """Carry a live EngineState across an engine-knob change (auto-tuner).

    The queue-schedule knobs are compile-time constants, so retuning means
    rebuilding the step function — but the state must survive. Knobs that
    leave the state pytree alone (``async_n``, ``rebalance_every``,
    ``rebalance_skew``, ``cell_order``, ``metrics``) return the state
    unchanged. The budget knobs (``max_migration``, ``max_births``) size
    ``EngineState.pending``, so those retunes flush the in-flight arrivals
    into their pre-claimed slots (exactly the scatter the next ingest would
    have done), rebuild the free-slot rings from the alive masks (the one
    full scan the ring design allows outside init), and attach empty
    pending blocks sized for the new config. Conservation is exact: the
    flush lands every pending row, and the carried rho already includes
    their deposits (merge-time correction), so ``pic.rho`` carries over
    untouched. The physics config must be identical — retargeting never
    reinterprets particles.
    """
    if old.pic != new.pic:
        raise ValueError(
            "retarget_state only retunes engine knobs; the physics config "
            "(EngineConfig.pic) must be identical")
    groups_old = _capacity_groups(old, mesh)
    groups_new = _capacity_groups(new, mesh)
    if (old.use_ring == new.use_ring and groups_old == groups_new
            and _group_pending_rows(old, groups_old)
            == _group_pending_rows(new, groups_new)):
        return state  # same pytree shape: the next compile picks it up

    def local(est: EngineState) -> EngineState:
        bufs = [jax.tree.map(lambda a: a[0], b) for b in est.pic.species]
        if old.use_ring:
            pend_in = [jax.tree.map(lambda a: a[0], p) for p in est.pending]
            for g, idxs in enumerate(groups_old):
                st = _flush_pending(
                    stack_species([bufs[i] for i in idxs]), pend_in[g])
                for j, i in enumerate(idxs):
                    bufs[i] = SpeciesBuffer(x=st.x[j], v=st.v[j], w=st.w[j],
                                            alive=st.alive[j])
        pic_out = PICState(species=tuple(_lift_tree(b) for b in bufs),
                           key=est.pic.key, step=est.pic.step,
                           rho=est.pic.rho)
        if not new.use_ring:
            return EngineState(pic=pic_out, rings=(), pending=())
        rings, pending = _engine_extras(new, mesh, bufs)
        return EngineState(
            pic=pic_out, rings=tuple(_lift_tree(rg) for rg in rings),
            pending=tuple(_lift_tree(p) for p in pending))

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(_state_specs(old, mesh),),
                      out_specs=_state_specs(new, mesh), check_vma=False)
    return jax.jit(f)(state)


def init_engine_state(ecfg: EngineConfig, mesh: Mesh,
                      seed: int = 0) -> EngineState:
    """Per-domain local init, sharded over the mesh domain axes."""
    cfg = ecfg.pic
    ncl = ecfg.local_nc(mesh)
    grid_local = Grid1D(nc=ncl, dx=cfg.dx)
    l_local = ncl * cfg.dx
    d = ecfg.num_domains(mesh)
    carried = _carries_rho(ecfg)
    use_ring = ecfg.use_ring
    groups = _capacity_groups(ecfg, mesh)

    def local_init() -> EngineState:
        r = halo.rank(ecfg.axis_names)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), r)
        keys = jax.random.split(key, len(cfg.species) + 1)
        bufs = []
        for i, sc in enumerate(cfg.species):
            cap_l = ecfg.local_cap(sc, mesh)
            n_l = sc.n_init // d
            b = init_uniform(keys[i], cap_l, n_l, l_local, sc.vth, sc.drift,
                             sc.weight)
            bufs.append(b)
        rho = None
        if carried:
            rho = jnp.zeros((ncl + 1,), jnp.float32)
            for idxs in groups:
                charges = jnp.asarray(
                    [cfg.species[i].charge for i in idxs], bufs[0].x.dtype)
                st = stack_species([bufs[i] for i in idxs])
                rho = rho + deposit_stacked(
                    grid_local, st.x, st.w, st.alive, charges)
        pic = _lift(bufs, keys[-1], jnp.zeros((), jnp.int32),
                    rho[None] if carried else None)
        if not use_ring:
            return EngineState(pic=pic, rings=(), pending=())
        rings, pending = _engine_extras(ecfg, mesh, bufs)
        return EngineState(
            pic=pic, rings=tuple(_lift_tree(rg) for rg in rings),
            pending=tuple(_lift_tree(p) for p in pending))

    specs_state = _state_specs(ecfg, mesh)
    init = jax.shard_map(local_init, mesh=mesh, in_specs=(),
                         out_specs=specs_state, check_vma=False)
    return jax.jit(init)()


# ------------------------------------------------------- checkpoint/restore
#
# The engine's side of the resilience layer (runtime/resilience.py drives
# it): `state_shape`/`state_shardings` give the `like` tree and layout for
# a bitwise typed restore onto the SAME domain count, and
# `resplit_host`/`elastic_state` are the elastic path onto D' != D —
# host-side compaction + re-split of the checkpointed queues, then a
# closed-form sharded rebuild (rings from alive counts, empty pending)
# that never runs the init-only full free-slot scan.


def state_shape(ecfg: EngineConfig, mesh: Mesh) -> EngineState:
    """Abstract EngineState (ShapeDtypeStructs) for this config on this
    mesh — the ``like`` tree of a bitwise checkpoint restore."""
    return jax.eval_shape(lambda: init_engine_state(ecfg, mesh, 0))


def state_shardings(ecfg: EngineConfig, mesh: Mesh) -> EngineState:
    """NamedShardings of the (device-lifted, global) EngineState leaves:
    leading device axis over the domain axes, step replicated — matches
    what ``init_engine_state`` produces and ``make_engine_step`` expects."""
    specs = _state_specs(ecfg, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def resplit_host(ecfg: EngineConfig, mesh: Mesh,
                 flat: dict, *, d_old: int):
    """Host-side elastic re-split of a checkpointed EngineState.

    ``flat`` is the ``{keypath: host array}`` dict of a checkpoint taken at
    ``d_old`` domains (``Checkpointer.restore_flat``). The steps mirror the
    retarget/rebalance machinery, on host numpy: flush every in-flight
    pending row into its pre-claimed slot (exactly the scatter the next
    ingest would have done), globalize positions, reassign each alive
    particle to its new domain by position, and compact per new domain
    (alive first, stable checkpoint order within a domain).

    Returns ``(species, counts)``: per-species dicts of ``(D', cap')``
    host arrays plus a ``(D', S)`` alive-count matrix — the closed-form
    inputs ``elastic_state`` rebuilds rings from without any full-capacity
    scan. Raises ``ValueError`` if a new domain's population exceeds its
    local capacity (re-split cannot invent headroom).
    """
    cfg = ecfg.pic
    d_new = ecfg.num_domains(mesh)
    if cfg.nc % d_old != 0 or cfg.nc % d_new != 0:
        raise ValueError(
            f"nc={cfg.nc} must divide both the checkpoint domains "
            f"({d_old}) and the current domains ({d_new})")
    l_old = (cfg.nc // d_old) * cfg.dx
    l_new = (cfg.nc // d_new) * cfg.dx
    nsp = len(cfg.species)

    # typed host buffers, one per species, with pending flushed in
    bufs = []
    for i in range(nsp):
        bufs.append({f: np.array(flat[f"pic/species/{i}/{f}"])
                     for f in ("x", "v", "w", "alive")})
    for g, idxs in enumerate(_capacity_groups_d(ecfg, d_old)):
        if f"pending/{g}/dest" not in flat:
            continue                      # legacy (use_ring=False) ckpt
        pend = {f: np.asarray(flat[f"pending/{g}/{f}"])
                for f in ("x", "v", "w", "alive", "dest")}
        for j, i in enumerate(idxs):
            cap_old = bufs[i]["x"].shape[1]
            ok = pend["alive"][:, j] & (pend["dest"][:, j] < cap_old)
            for r in range(d_old):
                dst = pend["dest"][r, j][ok[r]]
                bufs[i]["x"][r, dst] = pend["x"][r, j][ok[r]]
                bufs[i]["v"][r, dst] = pend["v"][r, j][ok[r]]
                bufs[i]["w"][r, dst] = pend["w"][r, j][ok[r]]
                bufs[i]["alive"][r, dst] = True

    species_out, counts = [], np.zeros((d_new, nsp), np.int32)
    for i, sc in enumerate(cfg.species):
        cap_new = _local_cap_d(ecfg, sc, d_new)
        b = bufs[i]
        alive = b["alive"].astype(bool)
        # globalize in f64 (exact for f32 inputs), localize, cast back
        off = l_old * np.arange(d_old, dtype=np.float64)[:, None]
        xg = b["x"].astype(np.float64) + off
        xs, vs, ws = xg[alive], b["v"][alive], b["w"][alive]
        r_new = np.clip(np.floor(xs / l_new).astype(np.int64), 0, d_new - 1)
        order = np.argsort(r_new, kind="stable")
        xs, vs, ws, r_new = xs[order], vs[order], ws[order], r_new[order]
        xdt = b["x"].dtype
        xl = (xs - r_new * l_new).astype(xdt)
        xl = np.clip(xl, xdt.type(0),
                     np.nextafter(xdt.type(l_new), xdt.type(0)))
        nx = np.zeros((d_new, cap_new), xdt)
        nv = np.zeros((d_new, cap_new, 3), b["v"].dtype)
        nw = np.zeros((d_new, cap_new), b["w"].dtype)
        na = np.zeros((d_new, cap_new), bool)
        for r in range(d_new):
            sel = r_new == r
            n_r = int(sel.sum())
            if n_r > cap_new:
                raise ValueError(
                    f"species {i}: {n_r} particles land on domain {r} but "
                    f"the local capacity at D={d_new} is {cap_new}")
            nx[r, :n_r], nv[r, :n_r] = xl[sel], vs[sel]
            nw[r, :n_r], na[r, :n_r] = ws[sel], True
            counts[r, i] = n_r
        species_out.append({"x": nx, "v": nv, "w": nw, "alive": na})
    return species_out, counts


def elastic_state(ecfg: EngineConfig, mesh: Mesh, species, counts,
                  key0, step: int = 0) -> EngineState:
    """Sharded EngineState from host-compacted per-domain buffers.

    ``species``/``counts`` come from ``resplit_host``. Rings are rebuilt in
    closed form from the alive counts (``ring_from_counts`` — compaction
    makes the free set a contiguous tail, so no full-capacity scan),
    pending starts empty, carried rho is re-deposited locally, and the
    per-domain RNG keys are re-derived as ``fold_in(key0, rank)`` (the same
    derivation ``init_engine_state`` uses). An elastic restart is therefore
    deterministic given the checkpoint, but not bitwise-continuous with the
    pre-failure RNG streams — see docs/resilience.md for the contract.
    """
    cfg = ecfg.pic
    ncl = ecfg.local_nc(mesh)
    grid_local = Grid1D(nc=ncl, dx=cfg.dx)
    carried = _carries_rho(ecfg)
    groups = _capacity_groups(ecfg, mesh)
    prows = _group_pending_rows(ecfg, groups)
    step_c = int(step)

    bufs_in = tuple(
        SpeciesBuffer(x=jnp.asarray(s["x"]), v=jnp.asarray(s["v"]),
                      w=jnp.asarray(s["w"]), alive=jnp.asarray(s["alive"]))
        for s in species)
    counts_in = jnp.asarray(np.asarray(counts), jnp.int32)
    key_in = jnp.asarray(np.asarray(key0))

    def local(sp, cnts, k0):
        r = halo.rank(ecfg.axis_names)
        key = jax.random.fold_in(k0, r)
        bufs = [jax.tree.map(lambda a: a[0], b) for b in sp]
        cl = cnts[0]                      # (S,) local alive counts
        rho = None
        if carried:
            rho = jnp.zeros((ncl + 1,), jnp.float32)
            for idxs in groups:
                charges = jnp.asarray(
                    [cfg.species[i].charge for i in idxs], bufs[0].x.dtype)
                st = stack_species([bufs[i] for i in idxs])
                rho = rho + deposit_stacked(
                    grid_local, st.x, st.w, st.alive, charges)
        pic = _lift(bufs, key, jnp.asarray(step_c, jnp.int32),
                    rho[None] if carried else None)
        if not ecfg.use_ring:
            return EngineState(pic=pic, rings=(), pending=())
        rings, pending = [], []
        for g, idxs in enumerate(groups):
            st = stack_species([bufs[i] for i in idxs])
            cg = jnp.stack([cl[i] for i in idxs])
            rings.append(
                jax.vmap(lambda c: ring_from_counts(c, st.capacity))(cg))
            pending.append(_empty_pending(
                len(idxs), prows[g], st.capacity, st.x.dtype))
        return EngineState(
            pic=pic, rings=tuple(_lift_tree(rg) for rg in rings),
            pending=tuple(_lift_tree(p) for p in pending))

    part = P(ecfg.axis_names)
    in_specs = (tuple(SpeciesBuffer(x=part, v=part, w=part, alive=part)
                      for _ in bufs_in), part, P())
    f = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                      out_specs=_state_specs(ecfg, mesh), check_vma=False)
    return jax.jit(f)(bufs_in, counts_in, key_in)
