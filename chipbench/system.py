"""The system under test, as the benchmark drives it.

This is the only module of the benchmark that imports the program
(``repro``). It builds the engine configuration of a cell from its data
files, initialises the state from the seed, compiles the one step the
window drives (``repro.distributed.engine.make_engine_step``), and reads
the step's outputs back as plain arrays: the effective particle state
(buffers with the in-flight arrivals and births landed in their claimed
slots), the diag counters, and the scope names of the compiled program.
"""

from __future__ import annotations

import math
import re

import numpy as np


def birth_budget(config: dict, traffic: dict) -> int:
    """Per-domain ionization birth budget: ``headroom`` times the expected
    events per step on one domain, rounded up to whole ``block``-row blocks
    per queue (the rule of ``chip_smoke.birth_budget``), so that
    ``birth_overflow`` stays 0."""
    ion = traffic.get("ionization")
    n_q = traffic["async_n"]
    if not ion:
        return n_q * traffic["birth_budget"]["block"]
    sp = {s["name"]: s for s in config["species"]}
    e, n = sp[ion["electron"]], sp[ion["neutral"]]
    length = config["grid"]["nc"] * config["grid"]["dx"]
    n_e = e["n_init"] * e["weight"] / length
    p = -math.expm1(-n_e * ion["rate"] * config["grid"]["dt"])
    rule = traffic["birth_budget"]
    per_queue = rule["headroom"] * n["n_init"] * p / config["domains"] / n_q
    return n_q * rule["block"] * math.ceil(per_queue / rule["block"])


def pic_config(config: dict, traffic: dict):
    """``repro.core.pic.PICConfig`` of a cell, built from its data files."""
    from repro.core import pic
    from repro.core.collisions import CollisionConfig

    names = [s["name"] for s in config["species"]]
    species = tuple(
        pic.SpeciesConfig(s["name"], float(s["charge"]), float(s["mass"]),
                          int(s["capacity"]), int(s["n_init"]),
                          vth=float(s["vth"]), weight=float(s["weight"]))
        for s in config["species"])
    ion = traffic.get("ionization")
    colls = tuple(
        CollisionConfig(c["kind"], names.index(c["species"]),
                        None if c.get("partner") is None
                        else names.index(c["partner"]), float(c["rate"]))
        for c in traffic.get("collisions", []))
    g = config["grid"]
    return pic.PICConfig(
        nc=int(g["nc"]), dx=float(g["dx"]), dt=float(g["dt"]),
        species=species, field_solve=bool(traffic["field_solve"]),
        boundary=g["boundary"], strategy=traffic.get("strategy", "unified"),
        ionization=None if not ion else (
            names.index(ion["neutral"]), names.index(ion["electron"]),
            names.index(ion["ion"])),
        ionization_rate=float(ion["rate"]) if ion else 0.0,
        ionization_vth_e=float(ion["vth_e"]) if ion else 1.0,
        collisions=colls, diag_every=int(traffic.get("diag_every", 1)))


def engine_config(config: dict, traffic: dict):
    from repro.distributed import engine

    return engine.EngineConfig(
        pic=pic_config(config, traffic), axis_names=("data",),
        async_n=int(traffic["async_n"]),
        max_migration=int(traffic["max_migration"]),
        max_births=birth_budget(config, traffic),
        cell_order=bool(traffic.get("cell_order", False)))


class Engine:
    """One cell's engine: configuration, mesh, state and compiled step."""

    def __init__(self, config: dict, traffic: dict):
        from repro.launch.mesh import make_debug_mesh

        self.config, self.traffic = config, traffic
        self.domains = int(config["domains"])
        self.ecfg = engine_config(config, traffic)
        self.mesh = make_debug_mesh(data=self.domains, model=1)
        self.devices = list(self.mesh.devices.flat)
        self.species = [s["name"] for s in config["species"]]
        self._init = None

    def init(self, seed: int):
        """The engine state of ``seed`` (``init_engine_state``). The seed
        enters the jitted init as an argument, not as a constant, so one
        compiled init, found in the persistent cache, serves every seed."""
        import jax
        from repro.distributed import engine

        if self._init is None:
            self._init = jax.jit(lambda s: engine.init_engine_state(
                self.ecfg, self.mesh, s))
        return self._init(np.uint32(seed % 2 ** 32))

    def compile(self, state):
        from repro.distributed import engine

        return engine.make_engine_step(self.ecfg, self.mesh).lower(
            state).compile()

    # ---- reading the step's outputs ------------------------------------

    def snapshot(self, state) -> dict:
        """Effective particle state on the host: ``{species: (x, v, alive)}``
        with x (D, C), v (D, C, 3), alive (D, C), the pending arrivals and
        births written into their claimed slots (what the next step's
        ingest lands). Copies; the device state is left as it is."""
        import jax

        pic = jax.device_get([(b.x, b.v, b.alive) for b in state.pic.species])
        pend = jax.device_get(state.pending)
        out = {}
        groups = self._groups()
        for g, idxs in enumerate(groups):
            p = pend[g]
            for j, i in enumerate(idxs):
                x, v, alive = (np.array(a) for a in pic[i])
                cap = x.shape[1]
                for d in range(x.shape[0]):
                    ok = p.alive[d, j] & (p.dest[d, j] < cap)
                    dest = p.dest[d, j][ok]
                    x[d, dest] = p.x[d, j][ok]
                    v[d, dest] = p.v[d, j][ok]
                    alive[d, dest] = True
                out[self.species[i]] = (x, v, alive)
        return out

    def counts(self, state) -> dict:
        """Effective alive count per species, from the state alone."""
        import jax

        alive = jax.device_get([b.alive for b in state.pic.species])
        pend = jax.device_get([(p.alive, p.dest) for p in state.pending])
        out = {}
        for g, idxs in enumerate(self._groups()):
            pa, pd = pend[g]
            for j, i in enumerate(idxs):
                cap = alive[i].shape[1]
                out[self.species[i]] = int(
                    alive[i].sum() + (pa[:, j] & (pd[:, j] < cap)).sum())
        return out

    def _groups(self):
        from repro.distributed import engine

        return engine._capacity_groups(self.ecfg, self.mesh)


def host_diag(diags) -> dict:
    """Per-step diag dicts (on the device) -> ``{key: (steps, ...) array}``."""
    import jax

    diags = jax.device_get(diags)
    return {k: np.stack([np.asarray(d[k]) for d in diags]) for k in diags[0]}


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_scopes(compiled) -> dict:
    """``{instruction name: op_name}`` of a compiled program's HLO. The
    op_name carries the ``jax.named_scope`` path (``.../engine/push/q0/...``)
    that the device trace's op events lack; the trace names its events by
    instruction."""
    out = {}
    for line in compiled.as_text().splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        out[m.group(1)] = op.group(1) if op else ""
    return out
