"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the result as the last line of standard output: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and, with
``--trace 1``, ``breakdown``), then ``checks``: each number compared with
its limit, also printed as the last lines of standard error. Exits non-zero,
printing no result, when JAX finds no TPU or fewer chips than the cell asks
for. Needs the repository's ``src/`` beside this directory.
"""

import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the script's own directory would shadow top-level modules: replace it
sys.path[:1] = [_ROOT, os.path.join(_ROOT, "src")]
# libtpu would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

if __name__ == "__main__":
    try:
        import repro  # noqa: F401  the system under test
        from bench.harness import main
    except ImportError as e:
        print(f"bench: cannot import the system under test: {e}", file=sys.stderr)
        sys.exit(2)
    sys.exit(main(t_process=T_PROCESS))
