"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout. The last line of standard output is one
JSON object (see ``harness.py``). Without a TPU, or with fewer chips than
the cell asks for, it prints no result and exits non-zero.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chipbench: no program under {ROOT}/src; run from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from repro.launch.cache import enable_compilation_cache

    # the program's own cache placement: JAX_COMPILATION_CACHE_DIR when
    # set, else the checkout's .jax_cache
    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from chipbench import harness

    return harness.main(sys.argv[1:], T_START)


if __name__ == "__main__":
    sys.exit(main())
