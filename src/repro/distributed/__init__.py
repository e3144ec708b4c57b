"""Asynchronous multi-device PIC engine (the paper's §4, TPU/JAX-native).

Concept map — how the paper's OpenMP/OpenACC asynchrony constructs land on
JAX/XLA primitives in this package (the expanded, per-phase version lives
in ``docs/architecture.md``):

=====================  =====================================================
Paper construct        JAX construct here
=====================  =====================================================
MPI rank / subdomain   mesh device under ``shard_map`` (``engine.py``); each
                       owns ``nc_global / D`` cells + its particle slabs
async(n) queues        ``EngineConfig.async_n`` interleaved slices of the
                       stacked (S, cap) particle buffer; a Python loop emits
                       one fused push + one migration ``ppermute`` per queue
``nowait``             queue k+1's push has no data dependency on queue k's
                       ``ppermute``, so XLA's latency-hiding scheduler
                       overlaps the collective with compute
``depend(in/out)``     the received packs are held as live SSA values
                       (double-buffered) and consumed only by the deferred
                       merge — the data-flow edges ARE the depend clauses
MPI_Isend/Irecv        ``jax.lax.ppermute`` of fixed-size send packs
BIT1 linked-list       ``particles.FreeSlotRing`` carried in ``EngineState``:
free-slot reuse        leavers and MC kills push their packed slot indices;
                       arrivals, ionization pair births (claimed under a
                       shared min-count budget) and SEE secondaries pop
                       pre-claimed slots; the scatter defers to the next
                       step's ingest — the merge never scans the buffers
MC sources (§3.3/SEE)  per-queue ``collisions.ionize_packed`` between push
                       and exchange (budgeted by ``max_births``); SEE off
                       the packed absorbed rows (``boundaries``); births
                       ride ``EngineState.pending``
Binary collisions      the ``collide`` phase: per-queue
(BIT1 MC menu)         ``collisions.apply_menu`` (cell-binned elastic /
                       charge-exchange / Takizuka–Abe Coulomb) between push
                       and the MC sources — velocities only, no ring traffic
OpenMP dynamic         ``EngineConfig.rebalance_every`` (period) and
scheduling             ``rebalance_skew`` (occupancy-skew trigger): compact
                       + interleaved re-split keeps per-queue occupancy
                       even (``queue_occ`` / ``queue_skew`` diagnostics);
                       ``cell_order=True`` makes the compact a counting sort
                       by cell (BIT1-style per-cell ordering)
MPI_Allgather (field)  eliminated: ``halo.py`` exchanges edge nodes with
                       ``ppermute`` and distributes the exact double-prefix
                       Poisson solve with scalar-only gathers
Nsight phase ranges    ``repro.obs.tracing`` named scopes on every phase /
                       queue stage / halo collective (Perfetto-visible; the
                       benchmark's per-layer metrics read them from the
                       compiled program)
Online knob tuning     ``repro.obs.autotune`` consumes the per-step metrics
                       stream (``EngineConfig.metrics``) and retunes the
                       queue knobs via ``engine.retarget_state``
=====================  =====================================================

``core/decomposition.py`` remains as a thin back-compat shim over this
package (same DomainConfig / make_distributed_step / init_distributed_state
API, async_n=1).
"""

from repro.distributed.engine import (EngineConfig, EngineState,
                                      attach_engine_state, init_engine_state,
                                      make_engine_step, retarget_state)

__all__ = [
    "EngineConfig", "EngineState", "attach_engine_state",
    "init_engine_state", "make_engine_step", "retarget_state",
]
