"""PIC cycle assembly — the Fig. 2 loop of the paper, single domain.

``make_step(cfg)`` builds a jit-compiled step closing over the static config.
The paper's benchmark configuration (``configs/pic_bit1.py``) disables the
field-solve phase (as its §3.3 test does) and exercises mover + MC ionization
only; the full cycle (deposit -> smooth -> Poisson -> E -> push -> collide)
is implemented and tested regardless.

Hot-loop structure (this file is the perf-critical assembly):

* same-capacity species are stacked into ONE ``StackedSpecies`` (S, cap)
  pytree and pushed with a single ``vmap``'d Boris kernel over the species
  axis — no per-species Python loop, and the field deposit collapses S
  sequential scatters into one flattened deposit;
* every mover strategy reports its wall-hit masks directly
  (``mover.PushResult``), so the plasma-wall emission source consumes the
  masks of THE push — each species is pushed exactly once per step;
* ``strategy='fused'`` deposits the post-push charge inside the push pass
  and the step carries that rho to the next field solve (``PICState.rho``),
  so particle arrays make one HBM round-trip per cycle instead of two;
* ``make_step``/``run`` donate the particle buffers to the step (XLA updates
  them in place rather than copying the full state every step), and
  ``diag_every`` rate-limits the full-buffer diagnostics reductions.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import collisions, diagnostics, fields, mover
from repro.core.params import RuntimeParams, b_active
from repro.core.grid import Grid1D, deposit, deposit_stacked
from repro.core.grid import deposit_flat
from repro.core.particles import (SpeciesBuffer, init_uniform, stack_species,
                                  unstack_species)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SpeciesConfig:
    name: str
    charge: float          # in units of e
    mass: float            # in units of m_e
    capacity: int
    n_init: int
    vth: float
    drift: float = 0.0
    weight: float = 1.0
    stride: int = 1        # sub-cycling: push every `stride` steps, dt*stride


@dataclasses.dataclass(frozen=True)
class PICConfig:
    nc: int = 1024
    dx: float = 1.0
    dt: float = 0.1
    species: Sequence[SpeciesConfig] = ()
    field_solve: bool = True
    smoothing_passes: int = 1
    strategy: mover.Strategy = "unified"
    gather_mode: str = "take"          # 'take' | 'onehot'
    boundary: mover.Boundary = "periodic"
    b_field: tuple[float, float, float] = (0.0, 0.0, 0.0)
    eps0: float = 1.0
    # ionization triple: indices into `species` (neutral, electron, ion)
    ionization: tuple[int, int, int] | None = None
    ionization_rate: float = 0.0
    ionization_vth_e: float = 1.0
    num_batches: int = 4               # for strategy='async_batched'
    # plasma-wall interaction (boundary='absorb'): (primary, target) index
    # pairs — absorbed primaries re-emit secondaries into target (SEE /
    # sputtering, BIT1's signature feature)
    wall_emission: tuple[tuple[int, int], ...] = ()
    emission_yield: float = 0.0
    emission_vth: float = 1.0
    emission_weight: float = 1.0       # macro-weight of emitted secondaries
    # binary-collision menu (elastic / charge-exchange / Coulomb), applied
    # after the push each step; collide_kernel routes the Takizuka–Abe pair
    # deflection through the Pallas kernel (interpret mode off-TPU)
    collisions: tuple[collisions.CollisionConfig, ...] = ()
    collide_kernel: bool = False
    # compute the full-buffer diagnostics reductions (counts, kinetic/field
    # energy) only every k-th step; off-steps report zeros
    diag_every: int = 1

    def __post_init__(self):
        # normalize to tuples: configs must stay hashable (they ride through
        # jit as static arguments in run())
        object.__setattr__(self, "species", tuple(self.species))
        object.__setattr__(self, "wall_emission",
                           tuple(tuple(p) for p in self.wall_emission))
        object.__setattr__(self, "collisions", tuple(self.collisions))
        object.__setattr__(self, "b_field", tuple(self.b_field))
        if self.ionization is not None:
            object.__setattr__(self, "ionization", tuple(self.ionization))
        collisions.validate_menu(self.collisions, self.species)
        over = [f"{sc.name} (n_init={sc.n_init} > capacity={sc.capacity})"
                for sc in self.species if sc.n_init > sc.capacity]
        if over:
            raise ValueError(
                "species initial population exceeds buffer capacity: "
                + ", ".join(over))
        if self.strategy not in mover.STRATEGIES:
            raise ValueError(
                f"unknown mover strategy {self.strategy!r}; valid strategies"
                f" are {mover.STRATEGIES}")
        if self.boundary not in mover.BOUNDARIES:
            raise ValueError(
                f"unknown boundary {self.boundary!r}; valid boundaries are "
                f"{mover.BOUNDARIES}")
        if self.diag_every < 1:
            raise ValueError(
                f"diag_every must be >= 1, got {self.diag_every}")
        if self.strategy == "async_batched":
            bad = [sc.name for sc in self.species
                   if sc.capacity % self.num_batches != 0]
            if bad:
                raise ValueError(
                    f"strategy='async_batched' needs num_batches "
                    f"({self.num_batches}) to divide every species capacity;"
                    f" offending species: {bad}")

    @property
    def grid(self) -> Grid1D:
        return Grid1D(nc=self.nc, dx=self.dx)

    @property
    def length(self) -> float:
        return self.nc * self.dx


@partial(jax.tree_util.register_dataclass,
         data_fields=("species", "key", "step", "rho"), meta_fields=())
@dataclasses.dataclass
class PICState:
    species: tuple[SpeciesBuffer, ...]
    key: Array
    step: Array
    # post-push charge density carried by the fused strategy (None otherwise):
    # deposited inside the push pass of step k, consumed by the field solve of
    # step k+1 — the positions are the same ones, just never re-read from HBM
    rho: Array | None = None


def _stackable(cfg: PICConfig) -> bool:
    """All species share one capacity -> the (S, cap) fast path applies."""
    return len(cfg.species) > 0 and len(
        {sc.capacity for sc in cfg.species}) == 1


def _carries_rho(cfg: PICConfig) -> bool:
    """The fused strategy may carry its in-pass deposit to the next field
    solve when every post-push charge change is accounted for. MC sources
    now are: ionization and wall-emission births are deposited into the
    carried rho as they land (the engine's arrival-style correction), and
    an ionized neutral must carry zero charge so its post-deposit death
    needs no correction. Sub-cycled (frozen) species remain excluded —
    their in-pass deposit would move charge the freeze puts back."""
    return (cfg.strategy == "fused" and cfg.field_solve
            and (cfg.ionization is None
                 or cfg.species[cfg.ionization[0]].charge == 0.0)
            and all(sc.stride == 1 for sc in cfg.species))


def kicked_rows(cfg: PICConfig, idxs) -> tuple[int, ...]:
    """The rows of the species ``idxs``, stacked in that order, whose
    electric kick can be nonzero: none when the config solves no field
    (E is zero), else the charged species' (a neutral has q/m = 0). The
    stacked mover gathers E for these rows alone."""
    if not cfg.field_solve:
        return ()
    return tuple(j for j, i in enumerate(idxs)
                 if cfg.species[i].charge != 0.0)


def init_state(cfg: PICConfig, seed: int = 0) -> PICState:
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, len(cfg.species) + 1)
    bufs = tuple(
        init_uniform(keys[i], sc.capacity, sc.n_init, cfg.length, sc.vth,
                     sc.drift, sc.weight)
        for i, sc in enumerate(cfg.species))
    rho = compute_rho(cfg, bufs) if _carries_rho(cfg) else None
    return PICState(species=bufs, key=keys[-1], step=jnp.zeros((), jnp.int32),
                    rho=rho)


def compute_rho(cfg: PICConfig, species: tuple[SpeciesBuffer, ...]) -> Array:
    """Total charge density: one flattened (S*cap,) deposit when the
    species stack, the per-species scatter loop otherwise."""
    grid = cfg.grid
    if _stackable(cfg):
        st = stack_species(species)
        charges = jnp.asarray([sc.charge for sc in cfg.species], st.x.dtype)
        return deposit_stacked(grid, st.x, st.w, st.alive, charges)
    rho = jnp.zeros((grid.ng,), jnp.float32)
    for sc, buf in zip(cfg.species, species):
        if sc.charge != 0.0:
            rho = rho + deposit(grid, buf, sc.charge)
    return rho


def field_from_rho(cfg: PICConfig, rho: Array) -> Array:
    """smooth -> Poisson -> E (the field phase after deposition)."""
    rho = fields.smooth_binomial(rho, cfg.smoothing_passes)
    phi = fields.solve_poisson(rho, cfg.dx, cfg.eps0)
    return fields.efield(phi, cfg.dx)


def compute_field(cfg: PICConfig, species: tuple[SpeciesBuffer, ...]) -> Array:
    """deposit rho -> smooth -> Poisson -> E (the field phase of the cycle)."""
    return field_from_rho(cfg, compute_rho(cfg, species))


def _b_arg(cfg: PICConfig, rp: RuntimeParams | None, dtype):
    """b for the push: traced array when params carry an active field,
    the static tuple otherwise (zero b keeps the no-rotation program)."""
    if rp is not None and b_active(cfg):
        return rp.b_field.astype(dtype)
    return cfg.b_field


def _push_all(state: PICState, cfg: PICConfig, e: Array,
              rp: RuntimeParams | None = None):
    """Push every species exactly once; returns (species list,
    per-species (hit_l, hit_r) masks, diag dict, fused rho | None)."""
    grid = cfg.grid
    diag: dict = {}
    hits: list[tuple[Array, Array]] = []
    new_rho = None
    carried = _carries_rho(cfg)

    if _stackable(cfg) and cfg.strategy in ("unified", "fused"):
        # ---- stacked fast path: one vmap'd push over the species axis ----
        st = stack_species(state.species)
        dtype = st.x.dtype
        qm = jnp.asarray([sc.charge / sc.mass for sc in cfg.species], dtype)
        dts = (jnp.asarray([cfg.dt * sc.stride for sc in cfg.species], dtype)
               if rp is None else rp.dts.astype(dtype))
        charges = (jnp.asarray([sc.charge for sc in cfg.species], dtype)
                   if carried else None)
        out, hl, hr, pdiag, new_rho = mover.push_stacked(
            st, e, grid, qm, dts, b=_b_arg(cfg, rp, dtype),
            boundary=cfg.boundary,
            gather_mode=cfg.gather_mode, charges=charges,
            gather_rows=kicked_rows(cfg, range(len(cfg.species))))
        strides = [sc.stride for sc in cfg.species]
        if any(s > 1 for s in strides):
            # sub-cycling (BIT1's nstep): heavy/neutral species push every
            # `stride` steps with dt*stride; frozen species keep their state
            do = jnp.mod(state.step, jnp.asarray(strides)) == 0      # (S,)
            def freeze(new, old):
                sel = do.reshape((-1,) + (1,) * (new.ndim - 1))
                return jnp.where(sel, new, old)
            out = jax.tree.map(freeze, out, st)
            hl = hl & do[:, None]
            hr = hr & do[:, None]
            pdiag = {k: jnp.where(do, v, jnp.zeros_like(v))
                     for k, v in pdiag.items()}
        species = list(unstack_species(out))
        for si, sc in enumerate(cfg.species):
            hits.append((hl[si], hr[si]))
            diag.update({f"{sc.name}/{k}": v[si] for k, v in pdiag.items()})
        return species, hits, diag, new_rho

    # ---- general path: per-species loop (explicit / async_batched, or
    #      heterogeneous capacities) ----
    if rp is not None and cfg.strategy == "explicit":
        raise NotImplementedError(
            "strategy='explicit' routes through the Pallas mover kernel, "
            "which bakes dt/qm as compile-time scalars; traced RuntimeParams "
            "are not supported there — use 'unified' or 'fused'")
    if rp is not None and cfg.strategy == "async_batched":
        # the lax.scan batching loop is FMA-contraction-sensitive: XLA:CPU
        # contracts mul+add inside the scan body when the kick scalar is a
        # runtime value but not when it is a literal, so a traced step could
        # not honor the bitwise static/traced contract (verified, 1-ulp v
        # diffs). The engine's async path (async_n queues + push_stacked)
        # computes qm*dt at runtime on BOTH paths and is parity-safe.
        raise NotImplementedError(
            "strategy='async_batched' cannot take traced RuntimeParams "
            "bitwise-safely (lax.scan FMA contraction differs between "
            "literal and traced kick scalars) — use 'unified' or 'fused'")
    if (rp is not None and cfg.strategy == "fused"
            and jax.default_backend() == "tpu" and not _stackable(cfg)):
        raise NotImplementedError(
            "strategy='fused' on TPU with heterogeneous capacities routes "
            "through the fused Pallas kernel, which bakes dt/qm as "
            "compile-time scalars; traced RuntimeParams are not supported "
            "there")
    species = []
    for si, (sc, buf) in enumerate(zip(cfg.species, state.species)):
        qm = sc.charge / sc.mass
        dt_s = cfg.dt * sc.stride if rp is None else rp.dts[si]
        kw = dict(b=_b_arg(cfg, rp, buf.x.dtype), boundary=cfg.boundary)
        if rp is not None:
            kw["qm_dt"] = rp.qm_dts[si]
        if cfg.strategy == "async_batched":
            kw["num_batches"] = cfg.num_batches
        if cfg.strategy != "explicit":
            kw["gather_mode"] = cfg.gather_mode
        if cfg.strategy == "fused" and carried and sc.charge != 0.0:
            kw["deposit_charge"] = sc.charge    # neutrals deposit nothing
        res = mover.push(buf, e, grid, qm, dt_s, strategy=cfg.strategy, **kw)
        pushed, hl, hr, d = res.buf, res.hit_left, res.hit_right, res.diag
        if res.rho is not None:
            new_rho = res.rho if new_rho is None else new_rho + res.rho
        if sc.stride > 1:
            do_push = jnp.mod(state.step, sc.stride) == 0
            pushed = jax.tree.map(lambda n, o: jnp.where(do_push, n, o),
                                  pushed, buf)
            d = jax.tree.map(lambda v: jnp.where(do_push, v, 0), d)
            hl = hl & do_push
            hr = hr & do_push
        species.append(pushed)
        hits.append((hl, hr))
        diag.update({f"{sc.name}/{k}": v for k, v in d.items()})
    return species, hits, diag, new_rho


def step_fn(state: PICState, cfg: PICConfig,
            params: RuntimeParams | None = None) -> tuple[PICState, dict]:
    """One PIC cycle. ``params`` (optional) supplies the runtime scalars as
    traced values; ``params=None`` keeps the classic static path where the
    config's values are baked into the program as constants. Both paths are
    bit-identical for equal values (see ``core/params.py``)."""
    rp = params
    grid = cfg.grid
    carried = _carries_rho(cfg)
    if not cfg.field_solve:
        e = jnp.zeros((grid.ng,), jnp.float32)
    elif carried and state.rho is not None:
        e = field_from_rho(cfg, state.rho)
    else:
        e = compute_field(cfg, state.species)

    key = state.key
    species, hits, diag, new_rho = _push_all(state, cfg, e, rp)

    if cfg.collisions:
        # collide right after the push (the engine's per-queue order): rates
        # come from beginning-of-step cell densities, pairing and scattering
        # act on the post-push velocities. Collisions touch only v — the
        # carried rho (positions/weights) needs no correction.
        key, sub = jax.random.split(key)
        dens = {i: collisions.cell_density(grid, state.species[i])
                for i in collisions.density_species(cfg.collisions)}
        bufs = {i: species[i]
                for i in collisions.involved_species(cfg.collisions)}
        bufs, cdiag = collisions.apply_menu(
            sub, bufs, cfg.collisions, dens, grid,
            cfg.dt if rp is None else rp.dt, cfg.collide_kernel,
            rates=None if rp is None else rp.collision_rates)
        for i, b in bufs.items():
            species[i] = b
        diag.update(cdiag)

    if cfg.wall_emission and cfg.boundary == "absorb":
        from repro.core.boundaries import EmissionParams, wall_emission
        eparams = EmissionParams(
            yield_=cfg.emission_yield if rp is None else rp.emission_yield,
            vth_emit=cfg.emission_vth,
            weight=cfg.emission_weight)
        for primary, target in cfg.wall_emission:
            key, sub = jax.random.split(key)
            hl, hr = hits[primary]
            species[target], d, erows = wall_emission(
                sub, species[primary], hl, hr, species[target], eparams,
                cfg.length)
            q_t = cfg.species[target].charge
            if carried and new_rho is not None and q_t != 0.0:
                # birth charge folds into the carried in-pass deposit
                new_rho = new_rho + deposit_flat(
                    grid, erows.x, q_t * erows.w * erows.ok)
            diag.update({f"{cfg.species[target].name}/{k}": v
                         for k, v in d.items()})

    if cfg.ionization is not None:
        ni, ei, ii = cfg.ionization
        key, sub = jax.random.split(key)
        iparams = collisions.IonizationParams(
            rate=cfg.ionization_rate if rp is None else rp.ionization_rate,
            vth_electron=cfg.ionization_vth_e)
        neu, ele, ion, d, births = collisions.ionize(
            sub, species[ni], species[ei], species[ii], grid, iparams,
            cfg.dt if rp is None else rp.dt)
        species[ni], species[ei], species[ii] = neu, ele, ion
        if carried and new_rho is not None:
            # one flattened deposit for both halves of every born pair; the
            # killed neutral carries no charge (see _carries_rho)
            q_e = cfg.species[ei].charge
            q_i = cfg.species[ii].charge
            bw = births.w * births.ok
            new_rho = new_rho + deposit_flat(
                grid, jnp.stack([births.x, births.x]),
                jnp.stack([q_e * bw, q_i * bw]))
        diag.update(d)

    species = tuple(species)

    def step_diag() -> dict:
        d = {}
        for sc, buf in zip(cfg.species, species):
            d[f"{sc.name}/count"] = buf.count()
            d[f"{sc.name}/ke"] = diagnostics.kinetic_energy(buf, sc.mass)
        if cfg.field_solve:
            d["field_energy"] = diagnostics.field_energy(e, grid, cfg.eps0)
        return d

    if cfg.diag_every > 1:
        # rate-limit the full-buffer reductions: lax.cond executes only the
        # taken branch, so off-steps skip the O(S*cap) sweeps entirely
        shapes = jax.eval_shape(step_diag)
        zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        diag.update(jax.lax.cond(
            jnp.mod(state.step, cfg.diag_every) == 0, step_diag,
            lambda: zeros))
    else:
        diag.update(step_diag())

    out = PICState(species=species, key=key, step=state.step + 1,
                   rho=new_rho if carried else state.rho)
    return out, diag


def make_step(cfg: PICConfig):
    """jit-compiled single step closing over the static config.

    The returned function is ``step(state, params=None)``: pass a
    ``RuntimeParams`` to trace the runtime scalars (one compile serves every
    parameter point), omit it to bake the config's values as constants.

    The state argument is DONATED: XLA reuses the particle buffers in place
    instead of copying the full state every step, so the previous state is
    invalid after the call (rebind, as in ``state, d = step(state)``).
    """
    def step(state: PICState, params: RuntimeParams | None = None):
        return step_fn(state, cfg, params)

    return jax.jit(step, donate_argnums=0)


@partial(jax.jit, static_argnames=("cfg", "steps"), donate_argnums=(0,))
def _run_scan(state: PICState, cfg: PICConfig, steps: int,
              params: RuntimeParams | None = None):
    def body(s, _):
        return step_fn(s, cfg, params)

    return jax.lax.scan(body, state, None, length=steps)


def run(cfg: PICConfig, steps: int, seed: int = 0,
        state: PICState | None = None,
        params: RuntimeParams | None = None) -> tuple[PICState, dict]:
    """Run `steps` steps under lax.scan; returns final state + stacked diag.

    The initial state is donated to the scan (see ``make_step``).
    """
    if state is None:
        state = init_state(cfg, seed)
    if _carries_rho(cfg) and state.rho is None:
        # warm-starting a fused run from a non-fused state: seed the carried
        # rho so the scan carry keeps one pytree structure throughout
        state = dataclasses.replace(
            state, rho=compute_rho(cfg, state.species))
    return _run_scan(state, cfg, steps, params)
