#!/usr/bin/env bash
# CI entry point: tier-1 tests + multi-device lane + docs, resilience and
# serving lanes. Performance is measured on the chip, by the benchmark in
# BENCHMARK.json and chipbench/, not here.
#
# Lane 1: the full tier-1 suite on the default single device (multi-device
#         tests spawn their own emulated-device subprocesses).
# Lane 2: the distributed-engine parity, slot-ring, MC-source
#         (ionization/SEE) and binary-collision tests again with 4 emulated
#         host devices IN-process (XLA_FLAGS) — exercises shard_map
#         collectives without the subprocess indirection.
# Lane 3: docs — no broken relative links in README.md / docs/, and the
#         README quickstart commands actually run (keep these in sync with
#         the "Quickstart" section of README.md), including an
#         observability smoke: --profile-dir trace capture + a metrics
#         JSONL stream validated against the schema.
# Lane 4: resilience — a 4-device in-process save -> kill -> resume ->
#         bitwise-compare smoke of the checkpoint/restore layer, plus the
#         CLI drill: --fail-at-step, --resume at the same D, then an
#         elastic --resume at a different D. The full matrix (D x async_n,
#         torn writes, elastic conservation) runs in lane 1 via
#         tests/test_resilience.py.
# Lane 5: serving — the simulation-as-a-service smoke: three sessions at
#         DISTINCT parameter points through a width-2 ensemble server
#         (submit -> step -> poll), asserting distinct final diagnostics,
#         slot reuse, and exactly ONE compile of the vmapped step; plus
#         the --ensemble CLI demo. The full contract (member-vs-solo
#         event parity, frozen slots) runs in lane 1 via
#         tests/test_ensemble.py / tests/test_serve.py.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

python -m pytest -x -q
XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    python -m pytest -x -q tests/test_async_engine.py tests/test_slot_ring.py \
    tests/test_mc_sources_engine.py tests/test_collisions_engine.py

# ---- docs lane ----
python scripts/check_links.py README.md docs
python -m repro.launch.pic_run --steps 2 --nc 256 --particles 4096
python -m repro.launch.pic_run --steps 2 --nc 256 --particles 4096 \
    --domains 2 --async-n 2 --rebalance-every 2 --field-solve
python -m repro.launch.pic_run --steps 2 --nc 256 --particles 4096 \
    --domains 2 --async-n 2 --rebalance-skew 64 --see-yield 0.5
python -m repro.launch.pic_run --steps 2 --nc 256 --particles 4096 \
    --domains 2 --async-n 2 --rebalance-every 2 --cell-order \
    --collisions elastic,cx,coulomb

# ---- observability smoke ----
rm -rf ci_profile_smoke
python -m repro.launch.pic_run --steps 2 --nc 256 --particles 4096 \
    --domains 2 --async-n 2 --profile-dir ci_profile_smoke \
    --metrics-jsonl ci_metrics_smoke.jsonl
test -n "$(find ci_profile_smoke -type f 2>/dev/null)" \
    || { echo "profile smoke wrote no trace files" >&2; exit 1; }
python - <<'EOF'
from repro.obs.metrics import read_jsonl, validate_record, validate_stream
header, steps = read_jsonl("ci_metrics_smoke.jsonl")
assert header is not None and steps, (header, len(steps))
errs = validate_stream([header] + steps)
assert not errs, errs
print(f"metrics smoke: header + {len(steps)} valid step records")
EOF
rm -rf ci_profile_smoke ci_metrics_smoke.jsonl

# ---- resilience lane ----
XLA_FLAGS="--xla_force_host_platform_device_count=4" python - <<'EOF'
import tempfile
import numpy as np
import jax
from repro.ckpt.checkpoint import Checkpointer
from repro.configs.pic_bit1 import make_engine_config, make_resilience_config
from repro.distributed import engine
from repro.launch.mesh import make_debug_mesh
from repro.runtime import resilience
from repro.runtime.fault_tolerance import FailureInjector, SimulatedFailure

ecfg = make_engine_config(make_resilience_config(nc=32, n=256), async_n=2,
                          max_migration=64, max_births=64)
mesh = make_debug_mesh(data=4, model=1)
step = engine.make_engine_step(ecfg, mesh)
ref, _ = resilience.run_engine(
    ecfg, mesh, engine.init_engine_state(ecfg, mesh, 0), num_steps=6,
    step_fn=step)
with tempfile.TemporaryDirectory() as tmp:
    ck = Checkpointer(tmp)
    try:
        resilience.run_engine(
            ecfg, mesh, engine.init_engine_state(ecfg, mesh, 0), num_steps=6,
            ckpt=ck, ckpt_every=2,
            injector=FailureInjector(fail_at_step=4), step_fn=step)
        raise SystemExit("injector did not fire")
    except SimulatedFailure:
        pass
    step_r, st = resilience.resume_engine(ecfg, mesh, ck)
    assert step_r == 4, step_r
    fin, _ = resilience.run_engine(ecfg, mesh, st, num_steps=6, step_fn=step)
for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(fin)):
    assert np.array_equal(np.asarray(a), np.asarray(b))
print("resilience smoke: save -> kill -> resume bitwise OK (D=4, async_n=2)")
EOF

# ---- serving lane ----
python - <<'EOF'
import numpy as np
from repro.configs.pic_bit1 import make_resilience_config
from repro.serve import SimService

svc = SimService(make_resilience_config(nc=64, n=256), width=2)
a = svc.submit({"dt": 0.3, "ionization_rate": 4e-3}, seed=1, steps=2)
b = svc.submit({"dt": 0.5, "emission_yield": 0.2}, seed=2, steps=3)
c = svc.submit({"dt": 0.7}, seed=3, steps=2)          # queued behind a/b
svc.run_until_drained()
polls = {s: svc.poll(s) for s in (a, b, c)}
assert all(p["status"] == "done" for p in polls.values()), polls
assert polls[c]["slot"] in (0, 1), polls[c]           # reused a freed slot
kes = [float(np.asarray(p["diag"]["e/ke"]).sum()) for p in polls.values()]
assert len({round(k, 9) for k in kes}) == 3, kes      # distinct physics
st = svc.stats()
assert st["compiles"] == 1, st                        # one executable
print(f"serving smoke: 3 sessions / 2 slots, distinct diags, "
      f"compiles={st['compiles']}")
EOF
python -m repro.launch.pic_run --steps 2 --nc 256 --particles 4096 \
    --strategy fused --ensemble 2

# ---- resilience CLI drill ----
rm -rf ci_ckpt_smoke
python -m repro.launch.pic_run --steps 8 --nc 256 --particles 4096 \
    --domains 2 --async-n 2 --ckpt-dir ci_ckpt_smoke --ckpt-every 2 \
    --fail-at-step 5
python -m repro.launch.pic_run --steps 8 --nc 256 --particles 4096 \
    --domains 2 --async-n 2 --ckpt-dir ci_ckpt_smoke --resume
python -m repro.launch.pic_run --steps 10 --nc 256 --particles 4096 \
    --domains 4 --async-n 2 --ckpt-dir ci_ckpt_smoke --resume
rm -rf ci_ckpt_smoke
