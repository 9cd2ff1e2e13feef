"""One run of a cell with a fault planted under the timed path, at the cell's
own size on the chip: the readings a limit's upper end is set from where no
variant of the reference can stand for the fault.

    python3 perfbench/fault_run.py --workload <cell> --seed <n> --seconds 20

The fault (`key_unchanged`): the momentum update left out. The key encoder is
put back to the seed's weights after every step, from a copy on the host: a
kept copy beside the step would not fit a cell whose state is half the chip.
The arguments are `run.py`'s; the last line of standard output is its result
object, whose `correct` should read false.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def key_unchanged(real):
    import jax

    kept = {}

    def step(state, rows, lengths, n):
        if not kept:    # host copy of the weights the harness put in: the step donates its state
            kept["k"] = jax.device_get(state.params_k)
        state, metrics = real(state, rows, lengths, n)
        return state.replace(params_k=jax.device_put(kept["k"])), metrics
    return step


def main(argv=None, platform="tpu"):
    from perfbench import run

    return run.main(argv, platform=platform, wrap_step=key_unchanged)


if __name__ == "__main__":
    sys.exit(main())
