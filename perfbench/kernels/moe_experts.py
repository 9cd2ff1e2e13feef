"""Operations and bytes of a routed expert layer's grouped products (gate, up,
down over rows sorted by expert), from shapes and the COUNTED rows alone: the
same work whatever implements it (`lax.ragged_dot` today). One forward pass of
one layer over `rows` assignments to `held` experts of `hidden x width`:

  gate, up: `[rows, hidden] x [hidden, width]` each; down: `[rows, width] x
  [width, hidden]`. Each expert's three matrices are read once, the rows are
  read for gate and for up, both results written, their product read, the
  result written.

A training step makes 4 forward-equivalents of them a layer: the key forward,
the query forward, and the query backward's two (a product's transpose is one
product for the rows' gradient and one for the weights'). The rematerialised
forward is not counted. SwiGLU's elementwise pass is not in the count: a fused
implementation moves no bytes for it.
"""

SCOPE = "moe_experts"
PASSES_PER_STEP = 4


def work(rows: float, held: int, hidden: int, width: int, itemsize: int) -> dict:
    """One forward pass of one layer: multiply-adds counted as two."""
    flops = 3 * 2 * rows * hidden * width
    weights = 3 * held * hidden * width * itemsize
    activations = (2 * rows * hidden + 2 * rows * width + rows * width + rows * hidden) * itemsize
    return {"flops": flops, "bytes": weights + activations}


def step_work(config_file: dict, rows_per_layer: float, itemsize: int) -> dict:
    one = work(rows_per_layer, config_file["num_experts"], config_file["hidden_size"],
               config_file["moe_intermediate_size"], itemsize)
    n = PASSES_PER_STEP * config_file["num_hidden_layers"]
    return {"flops": n * one["flops"], "bytes": n * one["bytes"]}
