"""The main path's kernels and steps, asked of the TPU's own compiler at
the 737M GPT's widths — for a v5e that is described, not attached
(on-chip-measurement guide, section 2.3).  Nothing runs; what the chip's
compiler would refuse (a tiling it cannot lower, too much VMEM, a
program that does not fit HBM) is refused here, at no chip time.

gpt.py / llama.py pick flash attention by jax.default_backend(), which
is "cpu" in such a compile, so the kernel and the engine's jitted steps
are compiled directly.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from ray_tpu.models import decode, gpt  # noqa: E402
from ray_tpu.ops import flash_attention as fa  # noqa: E402
from ray_tpu.serve.llm import engine  # noqa: E402

CFG = gpt.GPTConfig(vocab_size=32000, d_model=2048, n_heads=16, n_layers=12,
                    d_ff=8192, max_seq=1024, dtype=jnp.bfloat16, remat=False)


@pytest.fixture(scope="module")
def chip():
    """One device of a described v5e 2x2, with the persistent compile
    cache off: an entry written by such a compile cannot be read back
    without a chip, and the next compile would warn about it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it knows no such chip
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(chip, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        tree)


@pytest.mark.parametrize("shape", [(4, 16, 2048, 128), (8, 16, 4096, 128),
                                   (4, 16, 8192, 128)])
def test_flash_kernels_compile(chip, shape):
    """fwd, dq and dkv Pallas kernels at the 737M head shape."""
    qkv = _on(chip, [jax.ShapeDtypeStruct(shape, jnp.bfloat16)] * 3)
    grad = jax.jit(jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v).astype(jnp.float32)
        .sum(), argnums=(0, 1, 2)))
    text = grad.lower(*qkv).compile().as_text()
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("step", ["decode_tick", "prefill_chunk"])
def test_paged_step_compiles(chip, step):
    """The engine's own jitted decode tick ([8, 1]) and prefill chunk
    ([1, 32]) over a page pool of 8 rows x 1024 tokens."""
    rows, page, blocks = 8, 16, 1024 // 16
    params = _on(chip, jax.eval_shape(
        lambda: jax.tree_util.tree_map(
            lambda x: x.astype(CFG.dtype),
            gpt.init_params(CFG, jax.random.PRNGKey(0)))))
    cache = _on(chip, jax.eval_shape(
        lambda: decode.init_paged_cache(CFG, rows * blocks + 1, page)))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    if step == "decode_tick":
        lowered = engine._paged_tick.lower(
            params, i32(rows), i32(rows), cache, i32(rows, blocks), CFG,
            with_logits=False)
    else:
        lowered = engine._prefill_chunk.lower(
            params, i32(1, 32), i32(), cache, i32(1, blocks), CFG)
    mem = lowered.compile().memory_analysis()
    # params (1.5 GB bf16) + pool (0.8 GB) + temporaries, on a 16 GB chip
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8 << 30
