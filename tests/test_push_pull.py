"""push_pull numerics on a virtual 8-device mesh (2 dcn x 4 ici).

Reference coverage model (SURVEY.md §4): push_pull over many shapes/dtypes
== size x tensor (sum) or tensor (average); broadcast correctness from
root; handle poll/synchronize semantics.
"""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import byteps_tpu.jax as bps
from byteps_tpu.parallel.mesh import MeshSpec, build_mesh

try:
    from jax import shard_map
except ImportError:  # pragma: no cover
    from jax.experimental.shard_map import shard_map


def _init(dcn=2, ici=4):
    mesh = build_mesh(MeshSpec(dcn=dcn, ici=ici))
    bps.init(mesh=mesh)
    return mesh


@pytest.mark.parametrize("shape", [(8,), (3, 5), (1,), (17, 3, 2), (128, 9)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
def test_push_pull_sum_matches_numpy(shape, dtype):
    _init()
    n = 8
    rng = np.random.default_rng(42)
    if dtype == "int32":
        vals = rng.integers(-10, 10, size=(n,) + shape).astype(dtype)
    else:
        vals = rng.standard_normal((n,) + shape).astype("float32")
    x = jnp.asarray(vals).astype(dtype)
    out = bps.push_pull(x, average=False)
    expect = np.asarray(vals.astype("float64").sum(0))
    np.testing.assert_allclose(
        np.asarray(out, dtype="float64"), expect,
        rtol=3e-2 if dtype == "bfloat16" else 1e-5,
        atol=3e-2 if dtype == "bfloat16" else 1e-5)


def test_push_pull_average():
    _init()
    x = jnp.stack([jnp.full((6, 7), float(i)) for i in range(8)])
    out = bps.push_pull(x, average=True)
    np.testing.assert_allclose(np.asarray(out), np.full((6, 7), 3.5), rtol=1e-6)


def test_push_pull_tree_fused():
    _init()
    rng = np.random.default_rng(0)
    tree = {
        "w": jnp.asarray(rng.standard_normal((8, 4, 3)), jnp.float32),
        "b": jnp.asarray(rng.standard_normal((8, 5)), jnp.float32),
        "nested": {"k": jnp.asarray(rng.standard_normal((8, 2, 2, 2)),
                                    jnp.float32)},
    }
    out = bps.push_pull(tree, average=False)
    flat_in, treedef_in = jax.tree_util.tree_flatten(tree)
    flat_out, treedef_out = jax.tree_util.tree_flatten(out)
    assert treedef_in == treedef_out
    for i, o in zip(flat_in, flat_out):
        np.testing.assert_allclose(np.asarray(o), np.asarray(i).sum(0),
                                   rtol=1e-5, atol=1e-5)


def test_push_pull_inside_shard_map():
    """The hot path: push_pull called from per-device code in a jitted
    shard_map'd train-step-like function."""
    mesh = _init()
    n = 8

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=P(("dcn", "ici")),
             out_specs=P(("dcn", "ici")))
    def step(x):
        local = x  # [1, 5] shard per device
        g = bps.push_pull(local, average=True)
        return g

    x = jnp.arange(n * 5, dtype=jnp.float32).reshape(n, 5)
    out = step(x)
    # every device shard should hold the mean over the replica axis
    expect = np.tile(np.asarray(x).reshape(n, 5).mean(0), (n, 1)).reshape(n, 5)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)


def test_push_pull_ici_only_mesh():
    _init(dcn=1, ici=8)
    x = jnp.stack([jnp.full((3,), float(i + 1)) for i in range(8)])
    out = bps.push_pull(x, average=False)
    np.testing.assert_allclose(np.asarray(out), np.full((3,), 36.0))


def test_push_pull_odd_sizes_padding():
    """Sizes not divisible by ici axis exercise the padding path."""
    _init(dcn=2, ici=4)
    x = jnp.stack([jnp.full((7,), float(i)) for i in range(8)])  # 7 % 4 != 0
    out = bps.push_pull(x, average=False)
    np.testing.assert_allclose(np.asarray(out), np.full((7,), 28.0))


def test_async_handles():
    _init()
    x = jnp.ones((8, 4))
    h = bps.push_pull_async(x, average=False)
    res = bps.synchronize(h)
    assert bps.poll(h)
    np.testing.assert_allclose(np.asarray(res), np.full((4,), 8.0))


def test_wire_compression_bf16():
    _init()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, 33)), jnp.float32)
    out = bps.push_pull(x, average=True, compression=bps.Compression.bf16)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(x).mean(0),
                               rtol=2e-2, atol=2e-2)


def test_broadcast_parameters_inside_shard_map():
    mesh = _init(dcn=2, ici=4)

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=P(("dcn", "ici")),
             out_specs=P(("dcn", "ici")))
    def bcast(x):
        return bps.broadcast_parameters(x, root_rank=3)

    x = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)
    out = bcast(x)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 3.0))


def test_topology_queries():
    _init()
    # Horovod invariant: rank() in [0, size()) at the process level.
    assert bps.size() == jax.process_count() == 1
    assert bps.rank() == 0
    assert 0 <= bps.rank() < bps.size()
    # chip-level count is separate (the averaging denominator)
    assert bps.device_count() == 8
    assert bps.local_size() == 8


def test_requires_init():
    with pytest.raises(RuntimeError):
        bps.size()


def test_push_pull_int8_quantized_wire():
    """Compression.int8 routes through the quantized collective and stays
    within quantization tolerance of the exact mean."""
    import numpy as _np

    from byteps_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(dcn=2, ici=4))
    bps.init(mesh=mesh)
    rng = _np.random.default_rng(3)
    g = jnp.asarray(rng.standard_normal((8, 200)), jnp.float32)
    out = bps.push_pull({"g": g}, average=True,
                        compression=bps.Compression.int8)["g"]
    expect = _np.mean(_np.asarray(g), axis=0)
    _np.testing.assert_allclose(_np.asarray(out), expect, rtol=0.05,
                                atol=0.05)


def test_push_pull_int8_dcn_quantized_both_levels():
    """Compression.int8_dcn quantizes the slow cross-slice leg too (the
    same all-to-all + local-sum scheme per level); error stays within the
    compounded two-level quantization tolerance of the exact mean."""
    import numpy as _np

    from byteps_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(dcn=2, ici=4))
    bps.init(mesh=mesh)
    rng = _np.random.default_rng(4)
    g = jnp.asarray(rng.standard_normal((8, 4096)), jnp.float32)
    out = bps.push_pull({"g": g}, average=True,
                        compression=bps.Compression.int8_dcn)["g"]
    expect = _np.mean(_np.asarray(g), axis=0)
    err = _np.abs(_np.asarray(out) - expect)
    scale = _np.abs(_np.asarray(g)).max()
    assert err.max() <= 0.08 * scale, err.max() / scale
    # and the dcn-only degenerate mesh (single-chip slices) works too
    bps.shutdown()
    mesh2 = build_mesh(MeshSpec(dcn=8, ici=1))
    bps.init(mesh=mesh2)
    out2 = bps.push_pull({"g": g}, average=False,
                         compression=bps.Compression.int8_dcn)["g"]
    expect2 = _np.sum(_np.asarray(g), axis=0)
    err2 = _np.abs(_np.asarray(out2) - expect2)
    assert err2.max() <= 0.08 * _np.abs(expect2).max() + 0.5, err2.max()


# --- the reduction's shape follows the mesh -------------------------------
# One level ({dcn 1, ici n} or {dcn n, ici 1}): one all-reduce per leaf, in
# the leaf's own shape. Two levels: the fused reduce-scatter -> slow level
# -> all-gather over one flat buffer.

_MESHES = [(1, 8), (8, 1), (2, 4)]
_MESH_IDS = ["dcn1_ici8", "dcn8_ici1", "dcn2_ici4"]


def _odd_tree(rng, n=8):
    """Stacked per-replica values: odd sizes (7, 5x3), a scalar-shaped
    leaf, bf16 beside float32; 38 elements a replica."""
    def vals(*shape):
        return rng.standard_normal((n,) + shape).astype("float32")
    return {
        "a": jnp.asarray(vals(7)),
        "b": jnp.asarray(vals(5, 3)).astype(jnp.bfloat16),
        "s": jnp.asarray(vals()),
        "nested": {"c": jnp.asarray(vals(5, 3))},
    }


def _per_device(mesh, fn):
    """jit(shard_map(...)) of ``fn`` over stacked per-replica trees: each
    device sees its own replica's leaves without the leading axis."""
    spec = P(("dcn", "ici"))

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=spec, out_specs=spec)
    def run(tree):
        local = jax.tree_util.tree_map(lambda x: x[0], tree)
        return jax.tree_util.tree_map(lambda x: x[None], fn(local))
    return run


@pytest.mark.parametrize("average", [False, True], ids=["sum", "mean"])
@pytest.mark.parametrize("dcn,ici", _MESHES, ids=_MESH_IDS)
def test_tree_reduction_values_on_every_mesh_shape(dcn, ici, average):
    mesh = _init(dcn=dcn, ici=ici)
    tree = _odd_tree(np.random.default_rng(7))
    out = _per_device(
        mesh, lambda t: bps.push_pull(t, average=average))(tree)

    leaves_in, treedef_in = jax.tree_util.tree_flatten(tree)
    leaves_out, treedef_out = jax.tree_util.tree_flatten(out)
    assert treedef_in == treedef_out
    for i, o in zip(leaves_in, leaves_out):
        assert o.dtype == i.dtype and o.shape == i.shape
        # float32 accumulation of the replicas' values as they are (a bf16
        # leaf's eight values sum exactly in float32), rounded once to the
        # leaf's dtype: what a bf16 accumulator would not give.
        expect = np.asarray(i, dtype="float32").sum(0)
        if average:
            expect = expect / 8
        expect = np.asarray(jnp.asarray(expect).astype(i.dtype),
                            dtype="float32")
        got = np.asarray(o, dtype="float32")
        for replica in got:  # every device holds the same reduced leaf
            if i.dtype == jnp.bfloat16:
                np.testing.assert_array_equal(replica, expect)
            else:
                np.testing.assert_allclose(replica, expect,
                                           rtol=1e-6, atol=1e-6)


def test_bf16_leaf_is_summed_in_the_widest_dtype():
    """256 + 7 x 1 in a bf16 accumulator stays 256 in the worst order (257
    is no bf16 number); beside a float32 leaf the tree is summed in float32
    and the bf16 leaf comes back as bf16(263) = 264."""
    mesh = _init(dcn=1, ici=8)
    col = np.ones((8, 3), "float32")
    col[0] = 256.0
    tree = {"h": jnp.asarray(col).astype(jnp.bfloat16),
            "w": jnp.ones((8, 2), jnp.float32)}
    out = _per_device(mesh, lambda t: bps.push_pull(t, average=False))(tree)
    assert out["h"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(out["h"], dtype="float32"), np.full((8, 3), 264.0))


def _collectives(text):
    """(op, operand type) of every collective in lowered StableHLO text,
    in program order."""
    found = []
    for m in re.finditer(
            r'"stablehlo\.(all_reduce|reduce_scatter|all_gather)"', text):
        sig = re.search(r"\(tensor<([^>]*)>\) -> tensor<([^>]*)>",
                        text[m.end():])
        found.append((m.group(1), sig.group(1), sig.group(2)))
    return found


@pytest.mark.parametrize("dcn,ici", _MESHES, ids=_MESH_IDS)
def test_lowered_reduction_follows_the_mesh(dcn, ici):
    mesh = _init(dcn=dcn, ici=ici)
    tree = _odd_tree(np.random.default_rng(0))
    text = _per_device(
        mesh, lambda t: bps.push_pull(t, average=True)).lower(tree).as_text()
    ops = _collectives(text)
    if dcn > 1 and ici > 1:
        # 38 elements padded to 40 for four chips, float32 throughout:
        # the triple exactly as it was before the one-level path existed.
        assert ops == [("reduce_scatter", "40xf32", "10xf32"),
                       ("all_reduce", "10xf32", "10xf32"),
                       ("all_gather", "10xf32", "40xf32")]
        assert "stablehlo.concatenate" in text
        return
    # One level: one all-reduce per leaf, in the leaf's shape and in the
    # tree's widest dtype, and nothing of the tree's total size.
    assert sorted(ops) == sorted([
        ("all_reduce", "7xf32", "7xf32"), ("all_reduce", "5x3xf32", "5x3xf32"),
        ("all_reduce", "f32", "f32"), ("all_reduce", "5x3xf32", "5x3xf32")])
    for absent in ("stablehlo.concatenate", "stablehlo.pad",
                   "stablehlo.dynamic_update_slice", "tensor<38x",
                   "tensor<40x"):
        assert absent not in text


@pytest.mark.parametrize(
    "dcn,ici,calls", [(2, 4, [(10,)]), (8, 1, [(38,)]), (1, 8, [])],
    ids=["dcn2_ici4_gets_the_shard", "dcn8_ici1_gets_the_tree",
         "dcn1_ici8_unused"])
def test_dcn_reduce_fn_hook(dcn, ici, calls):
    """The PS hook stands in for the slow level, one call a tree: on two
    levels it receives the 1/ici shard of the fused buffer, on a dcn-only
    mesh the fused buffer whole; with no dcn level it is not called."""
    from byteps_tpu.parallel.hierarchical import tree_all_reduce

    mesh = _init(dcn=dcn, ici=ici)
    seen = []

    def hook(shard):
        seen.append(shard.shape)
        return lax.psum(shard, "dcn")

    tree = _odd_tree(np.random.default_rng(3))
    out = _per_device(mesh, lambda t: tree_all_reduce(
        t, average=False, dcn_reduce_fn=hook))(tree)
    assert seen == calls
    for i, o in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_allclose(
            np.asarray(o, dtype="float32")[0],
            np.asarray(i, dtype="float32").sum(0),
            rtol=2e-2 if i.dtype == jnp.bfloat16 else 1e-6, atol=1e-6)
