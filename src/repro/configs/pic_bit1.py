"""The paper's own benchmark configuration (§3.3), BIT1 ionization test.

Scenario: unbounded unmagnetized plasma of (e-, D+, D); electron-impact
ionization depletes neutrals, dn/dt = -n n_e R. One-dimensional grid of
~100K cells, three species, ~10M macro-particles per species (30M total),
1K time steps, field solver and smoother DISABLED (exactly the paper's
test); mover + MC ionization dominate — which is why the paper optimizes
the mover.

Grid/population sizes here are rounded to powers of two so they divide both
production meshes (16 and 32 domains); per-domain buffers get 1.6x headroom
over the initial load for ionization-born electrons/ions.
"""

from __future__ import annotations

import dataclasses

from repro.core import pic
from repro.core.collisions import CollisionConfig

NC_GLOBAL = 102_400            # ~100K cells
N_PER_SPECIES = 10_485_760     # ~10M macro-particles (x3 species = ~30M)
CAPACITY = 16_777_216          # 16Mi slots: 1.6x headroom, divides 16 & 32


def make_config(scale: int = 1, *, mover_strategy: str = "unified",
                boundary: str = "periodic",
                diag_every: int = 1) -> pic.PICConfig:
    """`scale` only asserts divisibility; sizes are global (the
    decomposition divides them by the domain count).

    ``mover_strategy`` accepts any of ``mover.STRATEGIES`` — including
    ``'fused'``, the single-pass push+deposit hot loop. ``diag_every``
    rate-limits the full-buffer diagnostics reductions (production runs want
    ~10-100; 1 reproduces the per-step trace the tests assert on).
    """
    assert NC_GLOBAL % max(scale, 1) == 0
    # weight 1.0 everywhere: the paper's test runs without the field solve,
    # so macro-weights only set the MC collision rates (n_e in P_ionize)
    species = (
        pic.SpeciesConfig("e", -1.0, 1.0, CAPACITY, N_PER_SPECIES, vth=1.0),
        pic.SpeciesConfig("D+", 1.0, 3672.0, CAPACITY, N_PER_SPECIES,
                          vth=0.016),
        pic.SpeciesConfig("D", 0.0, 3672.0, CAPACITY, N_PER_SPECIES,
                          vth=0.016),
    )
    return pic.PICConfig(
        nc=NC_GLOBAL, dx=1.0, dt=0.2, species=species,
        field_solve=False,                  # the paper's test disables it
        boundary=boundary,
        strategy=mover_strategy,
        ionization=(2, 0, 1), ionization_rate=1e-4, ionization_vth_e=1.0,
        diag_every=diag_every,
    )


def make_bench_config(nc: int = 4096, n: int = 262_144,
                      strategy: str = "unified",
                      diag_every: int = 1) -> pic.PICConfig:
    """Laptop-scale version (same physics): ``pic_run``'s default problem
    and the tests'."""
    cap = 2 * n
    species = (
        pic.SpeciesConfig("e", -1.0, 1.0, cap, n, vth=1.0),
        pic.SpeciesConfig("D+", 1.0, 3672.0, cap, n, vth=0.016),
        pic.SpeciesConfig("D", 0.0, 3672.0, cap, n, vth=0.016),
    )
    return pic.PICConfig(
        nc=nc, dx=1.0, dt=0.2, species=species, field_solve=False,
        boundary="periodic", strategy=strategy,
        ionization=(2, 0, 1), ionization_rate=1e-4, ionization_vth_e=1.0,
        diag_every=diag_every,
    )


def make_see_config(nc: int = 4096, n: int = 262_144,
                    strategy: str = "unified", emission_yield: float = 0.5,
                    emission_weight: float = 1.0,
                    diag_every: int = 1) -> pic.PICConfig:
    """Bounded-plasma variant: absorbing walls + secondary electron
    emission (electrons re-emit electrons — BIT1's signature plasma-wall
    source) on top of the ionization scenario. Runs single-domain or on
    the async engine (the SEE injector shares the free-slot ring path).
    ``emission_weight`` sets the macro-weight of the secondaries (< 1 for
    mixed-weight wall studies: many light secondaries per absorbed
    primary's worth of charge)."""
    cfg = make_bench_config(nc=nc, n=n, strategy=strategy,
                            diag_every=diag_every)
    return dataclasses.replace(
        cfg, boundary="absorb", wall_emission=((0, 0),),
        emission_yield=emission_yield, emission_vth=0.5,
        emission_weight=emission_weight)


def make_resilience_config(nc: int = 64, n: int = 1024,
                           strategy: str = "fused",
                           emission_yield: float = 0.7,
                           field_solve: bool = True,
                           diag_every: int = 1) -> pic.PICConfig:
    """The full-churn workload the resilience tests checkpoint: absorbing
    walls + SEE + MC ionization + the whole collision menu, with equal
    species capacities (one capacity group — the engine's collide/SEE
    paths assume the stacked layout) and the field solve ON so the carried
    rho rides along in ``PICState`` under ``strategy='fused'``. Every kind
    of state the checkpoint must capture — rings, pending migration AND
    birth blocks, carried rho, per-domain RNG keys — is exercised."""
    cap = 2 * n
    species = (
        pic.SpeciesConfig("e", -1.0, 1.0, cap, n, vth=1.0),
        pic.SpeciesConfig("D+", 1.0, 3672.0, cap, n, vth=0.02),
        pic.SpeciesConfig("D", 0.0, 3672.0, cap, n, vth=0.05),
    )
    return pic.PICConfig(
        nc=nc, dx=1.0, dt=0.5, species=species, field_solve=field_solve,
        boundary="absorb", strategy=strategy,
        collisions=make_collision_menu(),
        ionization=(2, 0, 1), ionization_rate=5e-3, ionization_vth_e=1.0,
        wall_emission=((0, 0),), emission_yield=emission_yield,
        emission_vth=0.5, diag_every=diag_every,
    )


# the menu aliases the launcher's --collisions flag accepts
COLLISION_MENU = ("elastic", "cx", "coulomb")


def make_collision_menu(menu=COLLISION_MENU, *, rate_elastic: float = 2e-3,
                        rate_cx: float = 2e-3, rate_coulomb: float = 1e-3
                        ) -> tuple[CollisionConfig, ...]:
    """The binary-collision menu over the (e-, D+, D) species triple:

    * ``elastic`` — electron elastic scattering off the neutral background
      (cell-binned density, speed-preserving isotropic rotation);
    * ``cx`` — resonant D+ <-> D charge exchange (within-cell identity
      swap, equal masses);
    * ``coulomb`` — intra-species e-e Coulomb scattering (Takizuka–Abe
      within-cell pairs, momentum/energy conserving).

    Rates fold the cross-section physics into one coefficient each (see
    ``collisions.CollisionConfig``); defaults give a few-percent collision
    probability per step at the bench-scale densities.
    """
    out = []
    for m in menu:
        if m == "elastic":
            out.append(CollisionConfig("elastic", 0, 2, rate_elastic))
        elif m in ("cx", "charge_exchange"):
            out.append(CollisionConfig("charge_exchange", 1, 2, rate_cx))
        elif m == "coulomb":
            out.append(CollisionConfig("coulomb", 0, None, rate_coulomb))
        else:
            raise ValueError(
                f"unknown collision menu entry {m!r}; valid entries are "
                f"{COLLISION_MENU + ('charge_exchange',)}")
    return tuple(out)


def make_collision_config(nc: int = 4096, n: int = 262_144,
                          menu=COLLISION_MENU, strategy: str = "unified",
                          diag_every: int = 1, **rates) -> pic.PICConfig:
    """The ``collisions`` bench scenario: the full binary-collision menu on
    the bench-scale (e-, D+, D) plasma with MC ionization OFF — isolates
    the collide phase the way ``transport`` isolates migration."""
    cfg = make_bench_config(nc=nc, n=n, strategy=strategy,
                            diag_every=diag_every)
    return dataclasses.replace(
        cfg, ionization=None, collisions=make_collision_menu(menu, **rates))


def make_engine_config(pic_cfg: pic.PICConfig | None = None, *,
                       async_n: int = 1, max_migration: int = 8192,
                       rebalance_every: int = 0, rebalance_skew: int = 0,
                       max_births: int = 8192,
                       cell_order: bool = False, metrics: bool = False,
                       axis_names: tuple[str, ...] = ("data",),
                       **bench_kw):
    """EngineConfig for the asynchronous multi-device engine, centralizing
    the queue-schedule knobs the launcher and the tests share.

    ``async_n`` is the paper's async(n) queue count, ``max_migration`` the
    per-species/direction/step send budget, ``max_births`` the analogous
    per-step ionization birth budget, ``rebalance_every`` the queue-adaptive
    re-split period (0 = off) and ``rebalance_skew`` the occupancy-skew
    threshold that additionally triggers the re-split (0 = off);
    ``cell_order=True`` makes the rebalance a BIT1-style counting sort by
    cell (per-cell ordering for the collide phase and deposit locality).
    ``metrics=True`` adds the observability counters to the
    step diagnostics (``repro.obs``; diagnostics-only, state unchanged).
    With no ``pic_cfg`` the CPU-scale bench config is built from
    ``bench_kw`` (see ``make_bench_config``).
    """
    from repro.distributed import engine  # deferred: keep configs light

    if pic_cfg is None:
        pic_cfg = make_bench_config(**bench_kw)
    return engine.EngineConfig(
        pic=pic_cfg, axis_names=axis_names, async_n=async_n,
        max_migration=max_migration, max_births=max_births,
        rebalance_every=rebalance_every, rebalance_skew=rebalance_skew,
        cell_order=cell_order, metrics=metrics)
