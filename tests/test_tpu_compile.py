"""Ahead-of-time compiles of the tile path for one TPU v5e chip.

Nothing runs on a chip here: the TPU compiler builds each program for a
described (not attached) v5e, which refuses what Mosaic or the chip's
memory would refuse — misaligned blocks, VMEM overflow, HBM overflow.
The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.engine import EngineConfig
from repro.engine.bucketing import BatchBucketKey, BucketKey
from repro.engine.registry import get_backend, tile_limit_error
from repro.kernels import ops
from repro.kernels.tiling import MAX_TILE_DEGREE, pick_tile_b

# One v5e chip's usable HBM, as the TPU compiler reports it ("15.75G").
V5E_HBM_BYTES = int(15.75 * 2 ** 30)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(sharding, name, n, d):
    tile = lambda dt: _spec(sharding, (n, d), dt)  # noqa: E731
    col = lambda dt: _spec(sharding, (n,), dt)  # noqa: E731
    seed = _spec(sharding, (), jnp.int32)
    i32, f32, b = jnp.int32, jnp.float32, jnp.bool_
    if name == "label_argmax":
        return ops.label_argmax, (tile(i32), tile(f32), tile(b), col(i32),
                                  seed), {}
    if name == "min_label":
        return ops.min_label, (tile(i32), tile(i32), tile(b), col(i32),
                               col(i32)), {}
    if name == "fused_move":
        return ops.fused_move, (tile(i32), tile(f32), tile(b), tile(b),
                                col(i32), col(b), col(b), col(b), col(b),
                                seed), {}
    return ops.fused_split, (tile(i32), tile(i32), tile(b), tile(b),
                             col(i32), col(i32)), {"prune": True}


@pytest.mark.parametrize("n,d", [(1024, 128), (1024, MAX_TILE_DEGREE),
                                 (120, 128), (1024, 8), (1024, 16),
                                 (1024, 32), (1024, 64), (65536, 8),
                                 (120, 8)],
                         ids=["d128", "widest", "exact_n120", "d8", "d16",
                              "d32", "d64", "road_d8", "exact_n120_d8"])
@pytest.mark.parametrize("name", ["label_argmax", "min_label", "fused_move",
                                  "fused_split"])
def test_kernel_compiles_for_v5e(one_chip, name, n, d):
    """Each tile kernel compiles to a Mosaic custom call at the tile that
    pick_tile_b chooses, at d=128, at the widest admitted width, at the
    narrow widths below 128 that degree-bounded graphs get (road-256's
    65,536 x 8 among them), and at the 120-row shape that
    ``bucketing="exact"`` produces."""
    fn, args, kw = _kernel_args(one_chip, name, n, d)
    compiled = fn.lower(*args, mode="pallas", **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pick_tile_b_is_mosaic_aligned():
    """Every tile is a multiple of 8 that divides n_pad, or n_pad itself."""
    for n_pad in (8, 16, 24, 40, 56, 120, 136, 1000, 1024, 4096, 12, 5, 1):
        for d in (8, 16, 32, 64, 128, 256, 384, 512, 640, 1024):
            t = pick_tile_b(n_pad, d)
            assert n_pad % t == 0, (n_pad, d, t)
            assert t % 8 == 0 or t == n_pad, (n_pad, d, t)


@pytest.mark.parametrize("n_bucket,d", [(1 << 20, 128),
                                        (1 << 17, MAX_TILE_DEGREE)],
                         ids=["cell_limit", "degree_limit"])
def test_largest_admitted_tile_plan_fits_hbm(one_chip, n_bucket, d):
    """The largest buckets the auto policy admits compile for one v5e with
    their propagate and split programs each in at most half its HBM (the
    rest holds the graph itself); twice the rows is refused."""
    assert tile_limit_error(n_bucket, d) is None
    assert tile_limit_error(2 * n_bucket, d) is not None
    plan = get_backend("tile").build(BucketKey(n_bucket, 2048, d),
                                     EngineConfig(kernel_mode="pallas"))
    r = plan.rows
    tiles = (_spec(one_chip, (r, d), jnp.int32),
             _spec(one_chip, (r, d), jnp.float32),
             _spec(one_chip, (r, d), jnp.bool_))
    col = lambda dt: _spec(one_chip, (r,), dt)  # noqa: E731
    n_real = _spec(one_chip, (), jnp.int32)
    programs = {
        "propagate": plan.propagate.lower(*tiles, n_real, col(jnp.int32),
                                          col(jnp.bool_)),
        "split": plan.split.lower(tiles[0], tiles[2], col(jnp.int32),
                                  col(jnp.int32), n_real),
    }
    for name, lowered in programs.items():
        compiled = lowered.compile()
        assert "tpu_custom_call" in compiled.as_text(), name
        ma = compiled.memory_analysis()
        total = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                 + ma.output_size_in_bytes)
        assert total <= V5E_HBM_BYTES // 2, (name, total)


@pytest.mark.parametrize("bucket", [BucketKey(1024, 4096, 128),
                                    BucketKey(65536, 262144, 8)],
                         ids=["d128", "road_d8"])
def test_tile_programs_name_kernels_and_sweep_scopes(one_chip, bucket):
    """The fused tile programs compiled for a v5e, at 128-wide rows and at
    the 8-wide rows of a degree-4 road graph, keep the kernel op names
    the trace readers match (``fused_move``, ``fused_split`` and their
    numbered copies) and carry the sweep scopes in their metadata."""
    import re
    plan = get_backend("tile").build(
        bucket, EngineConfig(kernel_mode="pallas", fuse_sweeps="on"))
    r, d = plan.rows, bucket.d
    tiles = (_spec(one_chip, (r, d), jnp.int32),
             _spec(one_chip, (r, d), jnp.float32),
             _spec(one_chip, (r, d), jnp.bool_))
    col = lambda dt: _spec(one_chip, (r,), dt)  # noqa: E731
    n_real = _spec(one_chip, (), jnp.int32)
    programs = {
        "fused_move": plan.propagate.lower(*tiles, n_real, col(jnp.int32),
                                           col(jnp.bool_)),
        "fused_split": plan.split.lower(tiles[0], tiles[2], col(jnp.int32),
                                        col(jnp.int32), n_real),
    }
    for kernel, lowered in programs.items():
        text = lowered.compile().as_text()
        calls = [line for line in text.splitlines()
                 if "tpu_custom_call" in line and " = " in line]
        assert calls, kernel
        for line in calls:
            name = line.split(" = ")[0].replace("ROOT", "").strip()
            assert re.fullmatch(rf"%{kernel}(\.\d+)?", name), name
            assert "/sweep.reduce/" in line, name
        for scope in ("sweep.gather", "sweep.wake"):
            assert f"/{scope}/" in text, (kernel, scope)


@pytest.mark.parametrize("bucket", [BatchBucketKey(128, 16384, 1 << 20, 1024)],
                         ids=["ego_k128_d1024"])
def test_batched_tile_programs_compile_at_hub_widths(one_chip, bucket):
    """The fused batched propagate and split programs at the ego-network
    cell's largest bucket (128 members, 16,384 rows, 1,024-wide rows)
    compile for one v5e, keep the kernel names the trace readers match,
    carry the sweep scopes, and fit in the chip's HBM."""
    import re
    plan = get_backend("tile").build_batch(
        bucket, EngineConfig(kernel_mode="pallas", fuse_sweeps="on"))
    r, d, k1 = plan.rows, bucket.d, bucket.k + 1
    i32, b = jnp.int32, jnp.bool_
    tile = lambda dt: _spec(one_chip, (r, d), dt)  # noqa: E731
    col = lambda dt: _spec(one_chip, (r,), dt)  # noqa: E731
    index = (_spec(one_chip, (k1,), i32), col(i32), col(i32))
    programs = {
        "fused_move": plan.propagate.lower(
            tile(i32), tile(jnp.float32), tile(b), *index,
            _spec(one_chip, (), i32), col(i32), col(b)),
        "fused_split": plan.split.lower(tile(i32), tile(b), *index,
                                        col(i32)),
    }
    for kernel, lowered in programs.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        calls = [line for line in text.splitlines()
                 if "tpu_custom_call" in line and " = " in line]
        assert calls, kernel
        for line in calls:
            name = line.split(" = ")[0].replace("ROOT", "").strip()
            assert re.fullmatch(rf"%{kernel}(\.\d+)?", name), name
            assert "/sweep.reduce/" in line, name
        for scope in ("sweep.gather", "sweep.wake"):
            assert f"/{scope}/" in text, (kernel, scope)
        ma = compiled.memory_analysis()
        total = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                 + ma.output_size_in_bytes)
        assert total <= V5E_HBM_BYTES // 2, (kernel, total)
