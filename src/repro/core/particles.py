"""Fixed-capacity SoA particle buffers — the JAX-native form of BIT1's lists.

BIT1 stores particles in per-cell linked lists; moving a particle between
cells means unlinking/relinking, and the per-cell counts are wildly uneven
(the source of the load imbalance the paper attacks with OpenMP tasks).

Under jit we cannot have dynamic shapes, so the TPU-native equivalent is a
dense structure-of-arrays buffer with a fixed capacity and an ``alive`` mask:

* the mover grids over *uniform tiles of particles* (not cells), which removes
  the load imbalance structurally instead of scheduling around it;
* per-cell operations (deposition, per-cell Monte-Carlo rates) become segment
  operations, optionally accelerated by a periodic counting sort by cell;
* birth (injection, ionization) writes into dead slots found by a prefix-sum
  slot allocator; death just clears the mask.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

Array = jax.Array

# ---- planar layout contract (shared by every Pallas kernel) ----------------
# TPU kernels view each particle array as (rows, LANES) planes so tiles are
# VREG-aligned. The contract lives HERE, next to the buffer type: a capacity
# that is a multiple of ``tile_rows * LANES`` round-trips through
# ``to_planes`` / ``from_planes`` as a zero-copy reshape; anything else pays
# one pad-concatenate per call (only tiny test buffers do).
LANES = 128


def plane_pad(a: Array, block: int, value=0.0) -> Array:
    """Pad axis 0 up to a multiple of ``block`` (no-op when already aligned)."""
    pad = (-a.shape[0]) % block
    if pad == 0:
        return a
    return jnp.concatenate(
        [a, jnp.full((pad,) + a.shape[1:], value, a.dtype)])


def to_planes(a: Array, tile_rows: int = 8, value=0.0) -> Array:
    """(cap,) -> (rows, LANES) with rows a multiple of ``tile_rows``."""
    return plane_pad(a, tile_rows * LANES, value).reshape(-1, LANES)


def from_planes(p: Array, capacity: int) -> Array:
    """(rows, LANES) -> (capacity,), dropping pad slots."""
    return p.reshape(-1)[:capacity]


@partial(jax.tree_util.register_dataclass,
         data_fields=("x", "v", "w", "alive"),
         meta_fields=())
@dataclasses.dataclass
class SpeciesBuffer:
    """SoA buffer for one species. All arrays share leading dim = capacity."""

    x: Array      # (cap,)   position, in [0, L)
    v: Array      # (cap, 3) velocity (1D3V: only v[:,0] couples to E_x)
    w: Array      # (cap,)   macro-particle weight
    alive: Array  # (cap,)   bool mask

    @property
    def capacity(self) -> int:
        return self.x.shape[0]

    def count(self) -> Array:
        return jnp.sum(self.alive.astype(jnp.int32))


@partial(jax.tree_util.register_dataclass,
         data_fields=("x", "v", "w", "alive"),
         meta_fields=())
@dataclasses.dataclass
class StackedSpecies:
    """All same-capacity species as one (S, cap) SoA pytree.

    The stacked form is what the fused PIC hot loop consumes: one ``vmap``'d
    Boris push over the species axis instead of a per-species Python loop,
    and one flattened (S*cap,) deposition instead of S sequential scatters.
    Per-species scalars (q/m, dt*stride, charge) travel as (S,) arrays
    broadcast against the capacity axis.
    """

    x: Array      # (S, cap)
    v: Array      # (S, cap, 3)
    w: Array      # (S, cap)
    alive: Array  # (S, cap)

    @property
    def num_species(self) -> int:
        return self.x.shape[0]

    @property
    def capacity(self) -> int:
        return self.x.shape[1]

    def counts(self) -> Array:
        return jnp.sum(self.alive.astype(jnp.int32), axis=1)


def stack_species(bufs: Sequence[SpeciesBuffer]) -> StackedSpecies:
    """Stack same-capacity species buffers into one (S, cap) pytree."""
    caps = {b.capacity for b in bufs}
    if len(caps) != 1:
        raise ValueError(f"stack_species needs equal capacities, got {caps}")
    return StackedSpecies(
        x=jnp.stack([b.x for b in bufs]),
        v=jnp.stack([b.v for b in bufs]),
        w=jnp.stack([b.w for b in bufs]),
        alive=jnp.stack([b.alive for b in bufs]))


def unstack_species(st: StackedSpecies) -> tuple[SpeciesBuffer, ...]:
    return tuple(
        SpeciesBuffer(x=st.x[s], v=st.v[s], w=st.w[s], alive=st.alive[s])
        for s in range(st.num_species))


def make_species(capacity: int, dtype=jnp.float32) -> SpeciesBuffer:
    """An empty (all-dead) buffer."""
    return SpeciesBuffer(
        x=jnp.zeros((capacity,), dtype),
        v=jnp.zeros((capacity, 3), dtype),
        w=jnp.zeros((capacity,), dtype),
        alive=jnp.zeros((capacity,), bool),
    )


def init_uniform(key: Array, capacity: int, n: int, length: float,
                 vth: float, drift: float = 0.0, weight: float = 1.0,
                 dtype=jnp.float32) -> SpeciesBuffer:
    """n live particles uniform in x, Maxwellian in v; rest of buffer dead."""
    kx, kv = jax.random.split(key)
    x = jax.random.uniform(kx, (capacity,), dtype, 0.0, length)
    v = vth * jax.random.normal(kv, (capacity, 3), dtype)
    v = v.at[:, 0].add(drift)
    alive = jnp.arange(capacity) < n
    w = jnp.full((capacity,), weight, dtype)
    return SpeciesBuffer(x=x, v=v, w=w * alive, alive=alive)


def cell_index(buf: SpeciesBuffer, dx: float, nc: int) -> Array:
    """Cell of each particle; dead particles are parked at cell == nc."""
    c = jnp.clip(jnp.floor(buf.x / dx).astype(jnp.int32), 0, nc - 1)
    return jnp.where(buf.alive, c, nc)


def counts_per_cell(buf: SpeciesBuffer, dx: float, nc: int) -> Array:
    """np[cell] — BIT1's per-cell particle counts (its ``np[isp][j]``)."""
    c = cell_index(buf, dx, nc)
    return jnp.zeros((nc + 1,), jnp.int32).at[c].add(1)[:nc]


def sort_by_cell(buf: SpeciesBuffer, dx: float, nc: int) -> SpeciesBuffer:
    """Counting-sort-equivalent reorder: live particles grouped by cell,
    dead particles pushed to the tail. Restores the memory locality BIT1
    gets from per-cell lists, without the lists."""
    key = cell_index(buf, dx, nc)  # dead -> nc sorts to the tail
    order = jnp.argsort(key, stable=True)
    return SpeciesBuffer(
        x=buf.x[order], v=buf.v[order], w=buf.w[order], alive=buf.alive[order])


def cell_bins(cell: Array, nc: int) -> tuple[Array, Array]:
    """Bin table of a cell-key array (dead/ineligible rows keyed ``nc``).

    Returns (counts, starts), both (nc + 1,): ``counts[c]`` rows carry key
    ``c`` and, in any stable sort by ``cell``, cell ``c`` occupies positions
    ``[starts[c], starts[c] + counts[c])`` — the segment boundaries the
    per-cell collision pairing gathers through. ``starts[nc]`` is the total
    live row count (the dead tail begins there). One scatter-add plus one
    (nc + 1,)-sized cumsum: bin-table cost scales with the CELL count, never
    with capacity."""
    counts = jnp.zeros((nc + 1,), jnp.int32).at[cell].add(1, mode="drop")
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    return counts, starts


def compact(buf: SpeciesBuffer) -> SpeciesBuffer:
    """Live particles first (stable). Cheap defragmentation."""
    order = jnp.argsort(~buf.alive, stable=True)
    return SpeciesBuffer(
        x=buf.x[order], v=buf.v[order], w=buf.w[order], alive=buf.alive[order])


def free_slots(buf: SpeciesBuffer, max_n: int) -> Array:
    """Indices of the first ``max_n`` dead slots (cap = sentinel overflow)."""
    return jnp.nonzero(~buf.alive, size=max_n, fill_value=buf.capacity)[0]


def inject_at(buf: SpeciesBuffer, dest: Array, x: Array, v: Array, w: Array,
              ok: Array) -> SpeciesBuffer:
    """Scatter candidates into pre-claimed dead slots (the gather-free half
    of injection).

    ``dest`` (M,) are slot indices already known to be dead — from
    ``free_slots`` or from a ``FreeSlotRing`` claim; ``ok`` masks the
    candidates that actually own a slot. Rejected candidates scatter to the
    ``capacity`` sentinel and drop. Both the full-scan ``inject_masked`` and
    the distributed engine's ring merge funnel through here, so the scatter
    semantics can never diverge.
    """
    dest = jnp.where(ok, dest, buf.capacity)
    return SpeciesBuffer(
        x=buf.x.at[dest].set(x, mode="drop"),
        v=buf.v.at[dest].set(v, mode="drop"),
        w=buf.w.at[dest].set(w, mode="drop"),
        alive=buf.alive.at[dest].set(True, mode="drop"),
    )


def inject_masked(buf: SpeciesBuffer, x: Array, v: Array, w: Array,
                  mask: Array) -> tuple[SpeciesBuffer, Array, Array]:
    """Write ``mask``-selected new particles into dead slots.

    x/v/w/mask have a fixed candidate length M. Returns
    (buffer, n_dropped, accepted): candidates that find no free slot are
    dropped and counted — BIT1 would realloc its lists; a fixed-capacity
    buffer surfaces the overflow instead. ``accepted`` marks the candidates
    that landed (the distributed engine deposits exactly those into the
    carried charge density).

    The slot search is a full-capacity ``free_slots`` scan per call; hot
    paths that inject every step should carry a ``FreeSlotRing`` instead and
    go straight to ``inject_at``.
    """
    m = x.shape[0]
    # rank of each candidate among the selected ones
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
    slots = free_slots(buf, m)                       # (m,) first m dead slots
    dest = jnp.where(mask, slots[jnp.clip(rank, 0, m - 1)], buf.capacity)
    ok = mask & (dest < buf.capacity)
    out = inject_at(buf, dest, x, v, w, ok)
    n_dropped = jnp.sum((mask & ~ok).astype(jnp.int32))
    return out, n_dropped, ok


# ---- persistent free-slot ring ---------------------------------------------
# ``inject_masked`` re-discovers dead slots with an O(capacity) ``nonzero``
# scan on every call — fine for occasional sources, but the distributed
# engine's migration merge injects every step, and that scan made the merge
# phase scale with total capacity instead of with the arrival count. The ring
# amortizes it: dead-slot indices are maintained INCREMENTALLY (killed /
# absorbed particles push their slot, injected arrivals pop one), so the
# steady-state cost is O(arrivals), independent of capacity. A full scan
# remains only at init and after a wholesale reorder (``compact`` /
# rebalance), where the free set is recomputed from the alive mask.


@partial(jax.tree_util.register_dataclass,
         data_fields=("slots", "head", "count"), meta_fields=())
@dataclasses.dataclass
class FreeSlotRing:
    """FIFO of currently-dead slot indices for one fixed-capacity buffer.

    ``slots`` is a circular buffer of length R >= the maximum number of
    simultaneously-free slots (R = capacity always suffices); entries at
    positions ``head .. head+count-1`` (mod R) are live, anything else is
    stale. Invariant: the live entries are exactly the dead slots of the
    buffer the ring tracks, minus slots already pre-claimed by in-flight
    arrivals — each listed at most once.
    """

    slots: Array   # (R,) int32 slot indices
    head: Array    # ()   int32 read cursor
    count: Array   # ()   int32 live entries

    @property
    def ring_capacity(self) -> int:
        return self.slots.shape[-1]


def ring_init(alive: Array) -> FreeSlotRing:
    """Build a ring from an alive mask (the one full O(cap) scan)."""
    cap = alive.shape[0]
    slots = jnp.nonzero(~alive, size=cap, fill_value=cap)[0].astype(jnp.int32)
    return FreeSlotRing(slots=slots, head=jnp.zeros((), jnp.int32),
                        count=jnp.sum((~alive).astype(jnp.int32)))


def ring_from_counts(alive_count: Array, cap: int) -> FreeSlotRing:
    """Ring for a freshly compacted buffer: free slots are [count, cap)."""
    ar = jnp.arange(cap, dtype=jnp.int32)
    slots = jnp.where(ar + alive_count < cap, ar + alive_count, cap)
    return FreeSlotRing(slots=slots, head=jnp.zeros((), jnp.int32),
                        count=(cap - alive_count).astype(jnp.int32))


def ring_push(ring: FreeSlotRing, idx: Array, ok: Array) -> FreeSlotRing:
    """Append the slots freed this step. ``idx`` (M,) are slot indices of
    particles that just died (killed, absorbed, migrated away); ``ok`` masks
    the real ones. O(M) — never scans the buffer."""
    r = ring.slots.shape[0]
    ok = ok.astype(bool)
    rank = jnp.cumsum(ok.astype(jnp.int32)) - 1
    pos = jnp.mod(ring.head + ring.count + rank, r)
    pos = jnp.where(ok, pos, r)                      # scatter-drop sentinel
    slots = ring.slots.at[pos].set(idx.astype(jnp.int32), mode="drop")
    return FreeSlotRing(slots=slots, head=ring.head,
                        count=ring.count + jnp.sum(ok.astype(jnp.int32)))


def ring_claim(ring: FreeSlotRing, want: Array, sentinel: int,
               budget: Array | None = None
               ) -> tuple[FreeSlotRing, Array, Array]:
    """Pop one slot per ``want`` candidate, in order.

    Returns (ring, dest, ok): ``dest`` (M,) holds a pre-claimed dead slot
    where ``ok``, the ``sentinel`` (typically the buffer capacity) where the
    candidate lost — either ``want`` was False or the ring ran dry (the
    caller reports those as drops). ``budget`` caps the grants below the
    ring's own count; paired claims on two rings (an ionization birth needs
    BOTH an electron and an ion slot) pass ``min(count_a, count_b)`` to both
    so the grant sets coincide and neither ring leaks a slot to a half-born
    pair. O(M)."""
    r = ring.slots.shape[0]
    want = want.astype(bool)
    rank = jnp.cumsum(want.astype(jnp.int32)) - 1
    avail = (ring.count if budget is None
             else jnp.minimum(ring.count, budget))
    ok = want & (rank < avail)
    pos = jnp.mod(ring.head + jnp.clip(rank, 0, r - 1), r)
    dest = jnp.where(ok, ring.slots[pos], sentinel)
    n = jnp.sum(ok.astype(jnp.int32))
    out = FreeSlotRing(slots=ring.slots, head=jnp.mod(ring.head + n, r),
                       count=ring.count - n)
    return out, dest, ok


def inject(buf: SpeciesBuffer, x: Array, v: Array, w: Array,
           mask: Array) -> tuple[SpeciesBuffer, Array]:
    """``inject_masked`` without the accepted mask (the common case)."""
    out, n_dropped, _ = inject_masked(buf, x, v, w, mask)
    return out, n_dropped


def kill(buf: SpeciesBuffer, mask: Array) -> SpeciesBuffer:
    """Mark ``mask`` particles dead (absorbed at wall, ionized away, ...)."""
    alive = buf.alive & ~mask
    return dataclasses.replace(buf, alive=alive, w=buf.w * alive)


def kill_packed(buf: SpeciesBuffer, idx: Array, ok: Array) -> SpeciesBuffer:
    """Kill the ``ok``-masked packed slot indices ``idx`` (M,).

    The packed mirror of ``inject_at``: MC sources that already hold their
    victims as packed indices (an ionization pack, a migration pack) kill
    through here, so the freed indices can feed ``ring_push`` with no
    additional scan."""
    gone = jnp.zeros((buf.capacity,), bool).at[
        jnp.where(ok.astype(bool), idx, buf.capacity)].set(True, mode="drop")
    return kill(buf, gone)


def first_true_indices(mask: Array, size: int) -> Array:
    """Indices of the first ``size`` True entries of the 1-D ``mask``, in
    order, padded with ``len(mask)``: bit for bit
    ``jnp.nonzero(mask, size=size, fill_value=len(mask))[0]``.

    ``jnp.nonzero`` with a size scatter-adds one update per entry of the
    mask (a ``bincount`` of its running count), whatever the mask holds.
    Here the k-th True is the first index where the running count reaches
    k, found by a binary search over it: ``size`` x log2(len) gathered
    elements and no scatter, for a few crossers in millions of slots."""
    count = jnp.cumsum(mask.astype(jnp.int32))
    return jnp.searchsorted(count, jnp.arange(1, size + 1, dtype=jnp.int32),
                            side="left", method="scan")


def take(buf: SpeciesBuffer, idx: Array) -> SpeciesBuffer:
    """Gather a sub-buffer (used to build migration send buffers)."""
    cap = buf.capacity
    valid = idx < cap
    idx_c = jnp.clip(idx, 0, cap - 1)
    return SpeciesBuffer(
        x=buf.x[idx_c] * valid,
        v=buf.v[idx_c] * valid[:, None],
        w=buf.w[idx_c] * valid,
        alive=buf.alive[idx_c] & valid,
    )
