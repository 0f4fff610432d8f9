"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode accepts block shapes, dtypes and matmuls that Mosaic refuses
on the chip, so the interpret-mode suites cannot show that the kernels
lower. These tests compile the window-tier pipeline kernel, the one-window
kernel, the block-pair boundary kernel and the whole ``skipper_match``
pipeline jit with ``interpret=False`` for a v5e that is described, not
attached (the TPU compiler is installed with jax), at the real geometry
W=2048, T=256, under both state specs. A kernel that Mosaic refuses fails here at no chip time.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports every
test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.statespec import StateSpec
from repro.kernels.skipper_match import ops
from repro.kernels.skipper_match.kernel import (
    build_boundary_matcher,
    build_pipeline_matcher,
    build_window_matcher,
)

W, T = 2048, 256
NUM_WINDOWS, NUM_ROWS, TILES_PER_WINDOW, BOUNDARY_TILES = 6, 3, 4, 5
SPECS = [StateSpec.u8(), StateSpec.legacy_i32()]
SPEC_IDS = ["u8", "legacy_i32"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without the chip, so keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_pipeline_kernel_lowers_for_v5e(spec, one_chip, no_compile_cache):
    fn = build_pipeline_matcher(
        NUM_ROWS, TILES_PER_WINDOW, T, W, 1, True, False, spec
    )
    slots = TILES_PER_WINDOW * T
    _compile(
        fn, one_chip,
        ((NUM_ROWS, slots), jnp.int32), ((NUM_ROWS, slots), jnp.int32),
        ((NUM_ROWS, W), spec.vmem_dtype),
    )


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_window_kernel_lowers_for_v5e(spec, one_chip, no_compile_cache):
    """The one-window kernel shares the tile body and the SMEM fallback
    count with the pipeline kernel."""
    fn = build_window_matcher(TILES_PER_WINDOW, T, W, 1, True, False, spec)
    n = TILES_PER_WINDOW * T
    _compile(fn, one_chip, ((n,), jnp.int32), ((n,), jnp.int32),
             ((W,), spec.vmem_dtype))


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_boundary_kernel_lowers_for_v5e(spec, one_chip, no_compile_cache):
    fn = build_boundary_matcher(
        BOUNDARY_TILES, T, NUM_WINDOWS, W, 1, True, False, spec
    )
    _compile(
        fn, one_chip,
        ((BOUNDARY_TILES,), jnp.int32), ((BOUNDARY_TILES,), jnp.int32),
        ((BOUNDARY_TILES, T), jnp.int32), ((BOUNDARY_TILES, T), jnp.int32),
        ((NUM_WINDOWS, W), spec.vmem_dtype),
    )


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_skipper_match_pipeline_lowers_for_v5e(spec, one_chip,
                                               no_compile_cache):
    """The whole compilation unit ``skipper_match(backend="pallas",
    interpret=False)`` runs: both kernels plus the decision gathers."""
    num_edges = 3000
    num_vertices = NUM_WINDOWS * W
    nb_pad = BOUNDARY_TILES * T
    fn = ops._build_pipeline(
        NUM_WINDOWS, NUM_ROWS, TILES_PER_WINDOW, T, W, nb_pad, num_edges,
        num_vertices, 1, False, "pallas", "auto", None, spec,
    )
    i32 = jnp.int32
    text = _compile(
        fn, one_chip,
        ((NUM_ROWS, TILES_PER_WINDOW * T), i32),
        ((NUM_ROWS, TILES_PER_WINDOW * T), i32),
        ((num_edges,), i32),
        ((BOUNDARY_TILES,), i32), ((BOUNDARY_TILES,), i32),
        ((nb_pad,), i32), ((nb_pad,), i32),
        ((NUM_ROWS,), i32),
        ((num_vertices,), i32),
    )
    assert text.count("tpu_custom_call") >= 2  # window tier + boundary
