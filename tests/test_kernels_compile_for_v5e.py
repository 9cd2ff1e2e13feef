"""The long-view kernels (ISSUE 33) compiled at the cell's widths for a described
v5e, without the chip: what interpret mode cannot show (a slice the tiling
refuses, more VMEM than a kernel may use, an int8 operand) fails here and not in a
chip call. The ONE file of the repository that describes a topology: only one
process at a time may load the TPU's library, so the call lives in a fixture and
nowhere a module is imported (`on-chip-measurement` guide, section 2)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, L, HEADS, KV, DIM = 2, 8192, 32, 4, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but cannot
    # be read back without the chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip) for shape, dtype in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled.memory_analysis()


def test_masked_attention_and_its_backward_compile_at_the_cells_size(one_chip):
    from moco_tpu.ops.pallas_attention import masked_attention

    def both(q, k, v, live, g):
        o, vjp = jax.vjp(lambda q, k, v: masked_attention(q, k, v, live, heads=HEADS, kv_heads=KV),
                         q, k, v)
        return o, vjp(g)

    q, kv = ((B, L, HEADS * DIM), jnp.bfloat16), ((B, L, KV * DIM), jnp.bfloat16)
    memory = _compile(both, one_chip, q, kv, kv, ((B, L, L), jnp.int8), q)
    assert memory.temp_size_in_bytes < 2 ** 28      # no `[heads, L, L]` tensor beside the operands


def test_a_shared_mask_of_positions_compiles_too(one_chip):
    from moco_tpu.ops.pallas_attention import masked_attention

    q, kv = ((B, L, HEADS * DIM), jnp.bfloat16), ((B, L, KV * DIM), jnp.bfloat16)
    _compile(lambda q, k, v, live: masked_attention(q, k, v, live, heads=HEADS, kv_heads=KV),
             one_chip, q, kv, kv, ((1, L, L), jnp.int8))


def test_the_index_scores_and_the_selection_compile_at_the_cells_size(one_chip):
    from moco_tpu.ops.pallas_select import index_scores, select_top_k

    _compile(index_scores, one_chip, ((B, L, 16, 64), jnp.bfloat16), ((B, L, 64), jnp.bfloat16),
             ((B, L, 16), jnp.float32))
    memory = _compile(lambda s: select_top_k(s, 2048), one_chip, ((B, L, L), jnp.float32))
    assert memory.output_size_in_bytes == B * L * L      # one byte a pair
