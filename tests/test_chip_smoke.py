"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and its
phases and checks pass on the CPU at a small size (4 host devices for the
multi-chip phase)."""

import dataclasses
import importlib.util
import os
import shutil
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")
SCRIPT = os.path.abspath(os.path.join(ROOT, "chip_smoke.py"))


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _small_cfg():
    from repro.configs.pic_bit1 import make_bench_config

    cfg = make_bench_config(nc=512, n=32768)
    return dataclasses.replace(cfg, species=tuple(
        dataclasses.replace(s, capacity=65536) for s in cfg.species))


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_without_a_tpu_exits_nonzero_naming_the_platform():
    out = _run([SCRIPT], ROOT)
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_alone_without_the_repository_exits_nonzero(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run(["chip_smoke.py"], tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_single_chip_phases_pass_on_cpu():
    from repro.configs.pic_bit1 import make_bench_config

    cs = _load()
    cfg = _small_cfg()
    single = cs.phase_single(cfg, 3, 0)
    engine = cs.phase_engine(cfg, 3, 0, single)
    assert single["ionized"] > 0 and engine["ionized"] > 0
    cs.phase_served(make_bench_config(nc=256, n=4096), 3, 0)


def check_multichip():
    """Run in a child with 4 host devices (no byte counts on the CPU)."""
    cs = _load()
    cs.memory = lambda device, key="peak_bytes_in_use": 1 << 40
    cs.phase_multichip(_small_cfg(), 2, 0)


def test_multichip_phase_passes_on_four_host_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.abspath(os.path.join(ROOT, "src")),
                    os.path.dirname(os.path.abspath(__file__))]))
    prog = "from test_chip_smoke import check_multichip; check_multichip()"
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "transport: D count D=4" in out.stdout
