"""`python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`

One run of one cell of `BENCHMARK.json`, in this one process. The last line of
standard output is the result object; without the chips the cell asks for the
exit code is non-zero and there is no result. See `harness.py`.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None, platform: str = "tpu", wrap_step=None, out=sys.stdout) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--root", default=ROOT, help="where the manifest and its paths lie")
    parser.add_argument("--manifest", default="BENCHMARK.json")
    args = parser.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "moco_tpu")):
        print("perfbench: the program (moco_tpu/) is not beside the benchmark", file=sys.stderr)
        return 2
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from perfbench import harness

    return harness.run(args, T_START if argv is None else time.perf_counter(),
                       platform=platform, wrap_step=wrap_step, out=out)


if __name__ == "__main__":
    sys.exit(main())
