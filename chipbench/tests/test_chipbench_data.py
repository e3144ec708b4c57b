"""The benchmark's data: cells found by name, configurations as published,
the work counts and peaks, and the run's refusals. No test here loads the
TPU library."""

import dataclasses
import hashlib
import importlib
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import cells, system, work  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_push_bytes():
    assert work.push_bytes(1) == 32
    assert work.push_bytes(31_457_280) == 32 * 31_457_280
    peaks = work.chip_peaks("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert work.push_floor_s(1_000_000, peaks) == pytest.approx(32e6 / 819e9)


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.chip_peaks("TPU v99")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(workload):
    cell = cells.resolve(workload)
    names = {m["name"] for m in cell.end_to_end}
    assert {"step_ms", "pushes_per_s", "hbm_peak_gb", "setup_s"} <= names
    assert cell.per_layer
    for m in cell.per_layer:
        mod = importlib.import_module(f"chipbench.layer_metrics.{m['name']}")
        assert mod.UNIT == m["unit"] and callable(mod.compute)
    assert cell.chips == cell.config["chips"]
    importlib.import_module(
        f"chipbench.references.{cell.traffic['reference']}")
    assert set(cell.traffic["limits"])


@pytest.mark.parametrize("mix", sorted(
    p.stem for p in (ROOT / "chipbench/traffic").glob("*.json")))
def test_every_mix_builds(mix):
    """Every traffic mix, in a cell or kept for one, builds the program's
    configuration and names a reference that reads each of its limits."""
    tr = json.loads((ROOT / f"chipbench/traffic/{mix}.json").read_text())
    cfg = json.loads((ROOT / "chipbench/configs/bit1_ss33.json").read_text())
    assert system.engine_config(cfg, tr).async_n == tr["async_n"]
    ref = importlib.import_module(f"chipbench.references.{tr['reference']}")
    src = Path(ref.__file__).read_text()
    assert tr["limits"] and all(f"``{k}``" in src or f'"{k}"' in src
                                or f"{k}=" in src for k in tr["limits"]
                                if k != "window_lost_rows")


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        cells.resolve("nope.ionize")


def test_benchmark_names_and_units():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for e in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] == \
            c["reduced"]


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A cell with a new traffic mix and a new per-layer metric resolves
    from new files and one entry; no file that was there changes."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")

    def digest():
        return {p: hashlib.sha256(p.read_bytes()).hexdigest()
                for p in (tmp_path / "chipbench").rglob("*") if p.is_file()}

    before = digest()
    mix = json.loads((tmp_path / "chipbench/traffic/ionize.json").read_text())
    mix["async_n"] = 4
    (tmp_path / "chipbench/traffic/ionize_q4.json").write_text(
        json.dumps(mix))
    (tmp_path / "chipbench/layer_metrics/merge_ms.py").write_text(
        "UNIT = 'ms/step'\n\ndef compute(ctx):\n    return None\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "bit1_ss33.ionize_q4",
                               "config": "bit1_ss33", "traffic": "ionize_q4",
                               "chips": 1, "why": "four queues"})
    bench["per_layer"].append({"name": "merge_ms", "unit": "ms/step",
                               "better": "lower", "source": "device_trace",
                               "layer": "engine bookkeeping",
                               "moves": "step_ms"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.resolve("bit1_ss33.ionize_q4", root=tmp_path)
    assert cell.traffic["async_n"] == 4
    assert "merge_ms" in {m["name"] for m in cell.per_layer}
    after = digest()
    assert {p: h for p, h in after.items() if p in before} == before


def test_configs_are_the_published_config():
    """bit1_ss33 with the ionize mix is ``configs/pic_bit1.make_config()``,
    and bit1_ss33_d4 is the same global problem."""
    from repro.configs.pic_bit1 import make_config

    ionize = json.loads((ROOT / "chipbench/traffic/ionize.json").read_text())
    for name in ("bit1_ss33", "bit1_ss33_d4"):
        cfg = json.loads((ROOT / f"chipbench/configs/{name}.json").read_text())
        assert system.pic_config(cfg, ionize) == make_config()


def test_birth_budget_is_the_smoke_rule():
    sys.path.insert(0, str(ROOT))
    import chip_smoke
    from repro.configs.pic_bit1 import make_config

    ionize = json.loads((ROOT / "chipbench/traffic/ionize.json").read_text())
    for name in ("bit1_ss33", "bit1_ss33_d4"):
        cfg = json.loads((ROOT / f"chipbench/configs/{name}.json").read_text())
        assert system.birth_budget(cfg, ionize) == chip_smoke.birth_budget(
            make_config(), domains=cfg["domains"])


def test_collide_mix_matches_the_menu():
    from repro.configs.pic_bit1 import make_collision_menu

    mix = json.loads((ROOT / "chipbench/traffic/collide.json").read_text())
    cfg = json.loads((ROOT / "chipbench/configs/bit1_ss33.json").read_text())
    pic = system.pic_config(cfg, mix)
    assert pic.collisions == make_collision_menu()
    assert pic.ionization is None
    assert dataclasses.replace(pic, collisions=(), ionization=(2, 0, 1),
                               ionization_rate=1e-4).species == \
        system.pic_config(cfg, json.loads(
            (ROOT / "chipbench/traffic/ionize.json").read_text())).species


def test_one_init_serves_every_seed():
    """The seed is an argument of the jitted init, not a constant: a new
    seed gives a new state and compiles nothing new."""
    import numpy as np

    from chipbench.tests.faults import small_cell

    cell = small_cell("bit1_ss33", "ionize")
    eng = system.Engine(cell.config, cell.traffic)
    a, b, c = (eng.init(s) for s in (3_000_000_019, 3_000_000_019, 17))
    xa, xb, xc = (np.asarray(s.pic.species[0].x) for s in (a, b, c))
    assert (xa == xb).all() and (xa != xc).any()
    assert eng._init._cache_size() == 1


def test_birth_velocity_z():
    """Sound Maxwellian births read a few sigma at most; births at rest or
    at twice the thermal speed read far off; arrivals are set apart."""
    import numpy as np

    from chipbench.references.ionize_step import _birth_velocity_z

    rng = np.random.default_rng(7)
    born = rng.normal(0.0, 1.0, (20_000, 3))
    arrivals = rng.normal(0.0, 5.0, (50, 3))
    both = np.concatenate([born, arrivals])
    assert abs(_birth_velocity_z(both, arrivals, 1.0)) < 5
    at_rest = np.concatenate([np.zeros_like(born), arrivals])
    assert _birth_velocity_z(at_rest, arrivals, 1.0) == pytest.approx(
        -(1.5 * 20_000) ** 0.5)
    assert _birth_velocity_z(np.concatenate([2 * born, arrivals]), arrivals,
                             1.0) > 100
    assert _birth_velocity_z(arrivals, arrivals, 1.0) == 0.0


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "bit1_ss33.ionize",
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
