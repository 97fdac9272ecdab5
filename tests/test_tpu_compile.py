"""The main path's Pallas kernels compile for a TPU v5e chip at published
widths (B=2048; the paper model's first expert layer, DIN's attention),
with no chip attached: the TPU compiler is asked for a described
``v5e:2x2`` topology and every compiled program must carry the kernel
(``tpu_custom_call``). Interpret mode accepts kernels the chip's compiler
refuses; these tests are what catches that without chip time.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and a worker that did so while
collecting would change which tests the others see.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.din_attention import din_attention
from repro.kernels.gather_einsum import gather_einsum
from repro.kernels.mari_matmul import mari_matmul_fused_groups

B = 2048                       # a coarse-ranking pool
# paper-ranking first expert layer: user side 4000 wide, item + cross 1000,
# expert width 512 (configs/paper_ranking.py)
D_USER, D_REST, D_EXPERT = 4000, 1000, 512
# DIN (configs/din.py): seq 100, embed 18, attention MLP 80-40
L, D, H1, H2 = 100, 18, 80, 40
DEVICE_SLOTS = 64              # the device rep tier's default slot count


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without one: keep it off."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_text(one_chip, no_persistent_cache):
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()
    return run


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("acc0_rows", [None, 1, B],
                         ids=["row", "acc0_row", "row_wise"])
def test_mari_matmul(compile_text, acc0_rows):
    """Eq. 7 accumulator init from the broadcast user row, plus a
    precomputed (1, d) or row-wise (B, d) partial."""
    def fn(xu, wu, xr, wr, b, *acc0):
        return mari_matmul_fused_groups([(xu, wu), (xr, wr)], b,
                                        acc0=acc0[0] if acc0 else None,
                                        activation="relu")
    shapes = [((1, D_USER), F32), ((D_USER, D_EXPERT), F32),
              ((B, D_REST), F32), ((D_REST, D_EXPERT), F32),
              ((D_EXPERT,), F32)]
    if acc0_rows is not None:
        shapes.append(((acc0_rows, D_EXPERT), F32))
    assert "tpu_custom_call" in compile_text(fn, *shapes)


@pytest.mark.parametrize("slots", [8, DEVICE_SLOTS])
def test_mari_matmul_gather(compile_text, slots):
    """Row-wise init gathered in the kernel from a (slots, d) rep table."""
    def fn(xr, wr, table, idx):
        return mari_matmul_fused_groups([(xr, wr)], acc0=table,
                                        user_index=idx, activation="relu")
    assert "tpu_custom_call" in compile_text(
        fn, ((B, D_REST), F32), ((D_REST, D_EXPERT), F32),
        ((slots, D_EXPERT), F32), ((B,), I32))


@pytest.mark.parametrize("slots", [8, DEVICE_SLOTS])
@pytest.mark.parametrize("spec,x_shape,t_shape", [
    ("bd,uldh->blh", (B, D), (L, D, H1)),    # query against the T table
    ("bl,uld->bd", (B, L), (L, D)),          # weights against the keys
])
def test_gather_einsum(compile_text, spec, x_shape, t_shape, slots):
    """Both decomposed-attention contractions the executor dispatches."""
    def fn(x, t, idx):
        return gather_einsum(spec, x, t, idx)
    assert "tpu_custom_call" in compile_text(
        fn, (x_shape, F32), ((slots,) + t_shape, F32), ((B,), I32))


def test_din_attention(compile_text):
    def fn(q, keys, mask, w1, b1, w2, b2, w3, b3):
        return din_attention(q, keys, mask, w1, b1, w2, b2, w3, b3)
    assert "tpu_custom_call" in compile_text(
        fn, ((B, D), F32), ((L, D), F32), ((L,), jnp.bool_),
        ((4 * D, H1), F32), ((H1,), F32), ((H1, H2), F32), ((H2,), F32),
        ((H2, 1), F32), ((1,), F32))
