"""Back-compat shim over ``repro.distributed`` — BIT1's MPI layer, TPU-native.

The domain-decomposed PIC step moved to the asynchronous multi-device engine
in ``repro/distributed/`` (async(n) queue scheduler in ``engine.py``,
halo-exchange field phase in ``halo.py``). This module keeps the seed's
public API — ``DomainConfig``, ``make_distributed_step``,
``init_distributed_state`` — delegating to the engine with ``async_n=1``,
so existing callers (launcher, dry-run, tests) keep working unchanged.

Differences from the seed implementation, inherited from the engine:

* migration overflow no longer loses particles: crossers that exceed the
  ``max_migration`` pack stay local (clamped, retried next step) and are
  reported via the ``migration_overflow`` diagnostic;
* the field phase is halo-based (edge-node ``ppermute`` + scalar-gather
  prefix Poisson) — the O(D * ng_local) full-rho ``all_gather`` and the
  redundant per-device global solve are gone;
* all species are pushed through the stacked vmap'd mover (the
  ``PICConfig.strategy`` choice still controls the carried in-pass deposit
  via ``'fused'``);
* the step donates its state buffers (rebind, as in ``state, d = step(state)``).
"""

from __future__ import annotations

import dataclasses

from jax.sharding import Mesh

from repro.core.pic import PICConfig, PICState
from repro.distributed import engine as _engine


@dataclasses.dataclass(frozen=True)
class DomainConfig:
    """Decomposition of a global PICConfig across mesh domain axes."""
    pic: PICConfig                       # cfg.nc == GLOBAL cell count
    axis_names: tuple[str, ...] = ("data",)
    max_migration: int = 2048            # per species/direction/step
    species_capacity_local: int | None = None  # default: global cap / D

    def to_engine(self, async_n: int = 1) -> _engine.EngineConfig:
        return _engine.EngineConfig(
            pic=self.pic, axis_names=tuple(self.axis_names),
            async_n=async_n, max_migration=self.max_migration,
            species_capacity_local=self.species_capacity_local)

    def num_domains(self, mesh: Mesh) -> int:
        return self.to_engine().num_domains(mesh)

    def local_nc(self, mesh: Mesh) -> int:
        return self.to_engine().local_nc(mesh)

    def local_cap(self, sc, mesh: Mesh) -> int:
        return self.to_engine().local_cap(sc, mesh)


def make_distributed_step(dcfg: DomainConfig, mesh: Mesh):
    """Build the shard_map'd PIC step for the given mesh (async_n=1)."""
    return _engine.make_engine_step(dcfg.to_engine(), mesh)


def init_distributed_state(dcfg: DomainConfig, mesh: Mesh,
                           seed: int = 0) -> PICState:
    """Per-domain local init, sharded over the mesh domain axes."""
    return _engine.init_engine_state(dcfg.to_engine(), mesh, seed)
