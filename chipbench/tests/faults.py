"""Whole runs of the harness at a size a CPU holds, sound and broken.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python -m chipbench.tests.faults

Each scenario drives ``harness.run_cell`` past its look for a chip, with
the compiled step the window drives wrapped so that it is sound or broken
in one way (or a step of the reference, with a fault planted in it, in the
program's place), and prints ``{scenario: {correct, checks}}`` as its last
line. The cells are the benchmark's own data files with the sizes cut to
a few thousand particles; everything else (traffic, limits, reference)
is as committed.

The same faults run on the chip at a cell's own size through
``chipbench/run.py --fault <name>`` (``FAULTS``), to read what each
number gives under it.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import cells, harness  # noqa: E402

MAX_MIGRATION = 64


def small_cell(config: str, traffic: str) -> cells.Cell:
    """A cell of the committed configuration and traffic files, cut to a
    CPU's size."""
    cfg = json.loads((cells.HERE / "configs" / f"{config}.json").read_text())
    cfg["grid"]["nc"] = 128 * cfg["domains"]
    for s in cfg["species"]:
        s["capacity"], s["n_init"] = 16384 * cfg["domains"], 4096 * cfg[
            "domains"]
    tr = json.loads((cells.HERE / "traffic" / f"{traffic}.json").read_text())
    if tr.get("ionization"):
        # ~64 ionizations per domain and step: 1.6% of the neutrals, where
        # the published cell ionizes 0.2%, so that a short run sees births
        tr["ionization"]["rate"] = 2.5e-3
    for c in tr.get("collisions", []):
        # a few percent of the rows collide per step at this density
        c["rate"] *= 5
    tr["max_migration"] = MAX_MIGRATION
    return cells.Cell(f"{config}.{traffic}", cfg["chips"], cfg, tr, (), ())


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


def _species_map(state, fn):
    pic = state.pic
    sp = tuple(fn(i, b) for i, b in enumerate(pic.species))
    return type(state)(pic=type(pic)(species=sp, key=pic.key, step=pic.step,
                                      rho=pic.rho),
                       rings=state.rings, pending=state.pending)


def unchanged(step, mm):
    """A step that returns its state unchanged."""
    def f(s):
        _, d = step(_copy(s))
        return s, d
    return f


def half_pushed(step, mm):
    """Half of the particles left out of the push: their slots keep the
    positions they had before the step."""
    def f(s):
        old = _copy(s)
        new, d = step(s)
        half = old.pic.species[0].x.shape[-1] // 2

        def keep(i, b):
            x = b.x.at[..., :half].set(old.pic.species[i].x[..., :half])
            return type(b)(x=x, v=b.v, w=b.w, alive=b.alive)
        return _species_map(new, keep), d
    return f


def x_altered(step, mm):
    """One particle's position altered where the step produces it."""
    def f(s):
        new, d = step(s)

        def bump(i, b):
            if i:
                return b
            j = jnp.argmax(b.alive[0])
            x0 = b.x[0, j]
            x = b.x.at[0, j].set(jnp.where(x0 < 64.0, x0 + 0.25, x0 - 0.25))
            return type(b)(x=x, v=b.v, w=b.w, alive=b.alive)
        return _species_map(new, bump), d
    return f


def v_altered(step, mm):
    """One electron's velocity altered where the step produces it."""
    def f(s):
        new, d = step(s)

        def bump(i, b):
            if i:
                return b
            j = jnp.argmax(b.alive[0])
            return type(b)(x=b.x, v=b.v.at[0, j, 1].add(0.5), w=b.w,
                           alive=b.alive)
        return _species_map(new, bump), d
    return f


def count_altered(step, mm):
    """The step's ionization count altered where it is produced."""
    def f(s):
        new, d = step(s)
        return new, dict(d, n_ionized=d["n_ionized"] + 1)
    return f


def no_exchange(step, mm):
    """The exchange between chips left out: the rows received from the
    neighbours never land (the first 2 * max_migration pending rows of each
    group are the arrivals)."""
    def f(s):
        new, d = step(s)
        pend = []
        for p in new.pending:
            arr = p.alive.at[..., :2 * mm].set(False)
            pend.append(type(p)(x=p.x, v=p.v, w=p.w, alive=arr, dest=p.dest))
        return type(new)(pic=new.pic, rings=new.rings,
                         pending=tuple(pend)), d
    return f


def births_at_rest(step, mm):
    """Newborn electrons given no velocity where the step produces them
    (the electron's pending rows past the 2 * max_migration arrivals are
    its births)."""
    def f(s):
        new, d = step(s)
        p = new.pending[0]
        v = p.v.at[:, 0, 2 * mm:].set(0.0)
        pend = (type(p)(x=p.x, v=v, w=p.w, alive=p.alive, dest=p.dest),)
        return type(new)(pic=new.pic, rings=new.rings,
                         pending=pend + tuple(new.pending[1:])), d
    return f


def e_collisions_skipped(step, mm):
    """The electron collisions skipped, their counters kept: every electron
    that stays in its slot leaves the step with the velocity it had."""
    def f(s):
        old = _copy(s)
        new, d = step(s)

        def revert(i, b):
            if i:
                return b
            a = old.pic.species[0]
            return type(b)(x=b.x, v=jnp.where((a.alive & b.alive)[..., None],
                                              a.v, b.v), w=b.w, alive=b.alive)
        return _species_map(new, revert), d
    return f


def coulomb_doubled(ref, phys, before, key):
    """The reference in float32 in the program's place, its Coulomb
    deflections drawn with twice the variance."""
    menu = tuple(dict(c, rate=2 * c["rate"]) if c["kind"] == "coulomb"
                 else c for c in phys.collisions)
    return ref.step(dataclasses.replace(phys, collisions=menu), before, key,
                    jnp.float32)


# name -> ("step", wrapper of the compiled step) or ("reference", a step in
# the program's place)
FAULTS = {
    "unchanged": ("step", unchanged),
    "half_pushed": ("step", half_pushed),
    "x_altered": ("step", x_altered),
    "v_altered": ("step", v_altered),
    "count_altered": ("step", count_altered),
    "no_exchange": ("step", no_exchange),
    "births_at_rest": ("step", births_at_rest),
    "e_collisions_skipped": ("step", e_collisions_skipped),
    "coulomb_doubled": ("reference", coulomb_doubled),
}

SCENARIOS = {
    "sound": (("bit1_ss33", "ionize"), None),
    "control": (("bit1_ss33", "ionize"), "control"),
    "unchanged": (("bit1_ss33", "ionize"), "unchanged"),
    "half_pushed": (("bit1_ss33", "ionize"), "half_pushed"),
    "x_altered": (("bit1_ss33", "ionize"), "x_altered"),
    "count_altered": (("bit1_ss33", "ionize"), "count_altered"),
    "births_at_rest": (("bit1_ss33", "ionize"), "births_at_rest"),
    "sound_collide": (("bit1_ss33", "collide"), None),
    "control_collide": (("bit1_ss33", "collide"), "control"),
    "unchanged_collide": (("bit1_ss33", "collide"), "unchanged"),
    "half_pushed_collide": (("bit1_ss33", "collide"), "half_pushed"),
    "v_altered_collide": (("bit1_ss33", "collide"), "v_altered"),
    "e_collisions_skipped_collide": (("bit1_ss33", "collide"),
                                     "e_collisions_skipped"),
    "coulomb_doubled_collide": (("bit1_ss33", "collide"), "coulomb_doubled"),
    "sound_d4": (("bit1_ss33_d4", "ionize"), None),
    "no_exchange_d4": (("bit1_ss33_d4", "ionize"), "no_exchange"),
}


def main(names=None) -> dict:
    out = {}
    with tempfile.TemporaryDirectory(prefix="chipbench-jax-cache-") as cache:
        # one compile per cell; the scenarios of a cell hit the cache
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        for name in names or SCENARIOS:
            files, fault = SCENARIOS[name]
            control = wrap = None
            if fault == "control":
                control = harness.bf16_control
            elif fault:
                kind, fn = FAULTS[fault]
                if kind == "step":
                    wrap = lambda st, fn=fn: fn(st, MAX_MIGRATION)  # noqa
                else:
                    control = fn
            res = harness.run_cell(small_cell(*files),
                                   3_000_000_000 + len(out), 0.02, False,
                                   time.perf_counter(), require_tpu=False,
                                   control=control, wrap_step=wrap)
            out[name] = {"correct": res["correct"],
                         "checks": {k: c["value"]
                                    for k, c in res["checks"].items()}}
    return out


if __name__ == "__main__":
    print(json.dumps(main(sys.argv[1:] or None)))
