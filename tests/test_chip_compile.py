"""The main-path Pallas kernels compile for a TPU v5e, at real widths.

Nothing runs here: each case lowers and compiles for a *described*
``v5e:2x2`` topology (the TPU compiler is installed; no chip is attached),
which refuses what interpret mode accepts — unsupported primitives,
misaligned tiles, VMEM overuse, a Mosaic call XLA would have to partition.
Real widths: d=128, metadata padded to 128 lanes, kpad 16 (k=10) and 64
(the int8 path's 4x over-fetch), pack bucket blocks of 16 rows x 65536.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

D = 128
TN = 256
TQ = 64


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def mosaic(topo):
    """Compile kernels with Mosaic although this process's backend is the
    CPU: the kernels' backend-derived mode is steered here, in the test,
    and the persistent compile cache stays off (entries written for a
    described chip cannot be read back without one)."""
    ft = importlib.import_module("repro.kernels.filtered_topk")
    qt = importlib.import_module("repro.kernels.quant_topk")
    gt = importlib.import_module("repro.kernels.graph_topk")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    with pytest.MonkeyPatch.context() as mp:
        for mod in (ft, qt, gt):
            mp.setattr(mod, "interpret_mode", lambda: False)
        jax.clear_caches()
        yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices).reshape(4), ("shard",),
                axis_types=(AxisType.Auto,))


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("kind, per_row", [
    pytest.param("box", False, id="box"),
    pytest.param("box_ball", False, id="box_ball"),
    pytest.param("box", True, id="box-per_row"),
    pytest.param("box_ball", True, id="box_ball-per_row")])
@pytest.mark.parametrize("kpad", [16, 64])
def test_fused_fp32_kernel_compiles(mosaic, one_chip, kind, per_row, kpad):
    """One filter shared by the query tile, or one per query row (the
    folded grouped path: params ``[TQ, 4, 128]``, 4 real metadata dims)."""
    from repro.kernels.filtered_topk import filtered_topk_kernel_call
    f = lambda q, x, s, p: filtered_topk_kernel_call(
        q, x, s, p, kind=kind, kpad=kpad, tq=TQ, tn=TN,
        m=4 if per_row else None, interpret=False)
    params = (TQ, 4, 128) if per_row else (4, 128)
    c = _compiled(f, _spec((TQ, D), jnp.float32, one_chip),
                  _spec((65536, D), jnp.float32, one_chip),
                  _spec((65536, 128), jnp.float32, one_chip),
                  _spec(params, jnp.float32, one_chip))
    out = c.memory_analysis().output_size_in_bytes
    assert out <= 2 * TQ * max(128, 2 * kpad) * 4 + 4096


@pytest.mark.parametrize("kpad", [16, 64])
def test_int8_kernel_compiles(mosaic, one_chip, kpad):
    from repro.kernels.ops import quant_meta_rows
    from repro.kernels.quant_topk import quant_filtered_topk_kernel_call
    mq = quant_meta_rows(4)
    f = lambda q, c, st, p: quant_filtered_topk_kernel_call(
        q, c, st, p, kind="box_ball", kpad=kpad, tq=TQ, tn=TN,
        interpret=False)
    _compiled(f, _spec((TQ, D), jnp.float32, one_chip),
              _spec((D, 65536), jnp.int8, one_chip),
              _spec((mq, 65536), jnp.float32, one_chip),
              _spec((4, mq), jnp.float32, one_chip))


@pytest.mark.parametrize("c", [512, 4096, 4100])
def test_beam_step_kernel_compiles(mosaic, one_chip, c):
    """8 query rows x c gathered candidates: one traversal hop (width 8 x
    degree 64 = 512) and the stitched seed set of a 32-segment bucket
    (4096, and a length the candidate tile does not divide)."""
    from repro.kernels.graph_topk import beam_step_scores
    f = lambda q, cx, cm, p: beam_step_scores(q, cx, cm, p, kind="box_ball",
                                              interpret=False)
    _compiled(f, _spec((8, D), jnp.float32, one_chip),
              _spec((8, c, D), jnp.float32, one_chip),
              _spec((8, c, 128), jnp.float32, one_chip),
              _spec((4, 128), jnp.float32, one_chip))


@pytest.mark.parametrize("grouped", [False, True])
def test_bucket_dispatch_compiles(mosaic, one_chip, grouped):
    """The per-bucket vmapped dispatch over a [16, 65536, ·] block, solo
    and for a filter class folded into one 64-row query tile, each row
    with its own filter, temporal mask and k (kernel and shard merge in
    one program)."""
    from repro.kernels import ops
    blk = (_spec((16, 65536, D), jnp.float32, one_chip),
           _spec((16, 65536, 128), jnp.float32, one_chip))
    if grouped:
        disp = ops._grouped_kernel_dispatch(
            "box", 16, "l2", TQ, TN, ops._grouped_k_top(16, 16, 65536), 4)
        args = (_spec((TQ, D), jnp.float32, one_chip),
                _spec((TQ, 4, 128), jnp.float32, one_chip),
                _spec((TQ, 16), jnp.bool_, one_chip),
                _spec((TQ,), jnp.int32, one_chip), *blk,
                _spec((16, 65536), jnp.int32, one_chip))
    else:
        disp = ops._sharded_kernel_dispatch("box", 16, "l2", TQ, TN)
        args = (_spec((TQ, D), jnp.float32, one_chip), *blk,
                _spec((4, 128), jnp.float32, one_chip))
    text = _compiled(disp, *args).as_text()
    # the device trace names the kernel's op after this instruction, behind
    # one "vmap_" per batching axis
    assert re.search(r"^\s*%(vmap_)*jit_filtered_topk_kernel_call", text,
                     re.M)
    if grouped:
        # the merge takes its k rounds without a sort: each padded row
        # count compiles its own program, and a sort's code (about 300 KB
        # on the chip) would stay in device memory once per program
        assert not re.search(r"\bsort\(", text)


@pytest.mark.parametrize("mode", ["fp32", "int8"])
def test_sharded_dispatch_compiles_on_mesh(mosaic, mesh4, mode):
    """Over a 4-device "shard" mesh each device runs the kernel on its
    resident rows: one Mosaic call, no block gathered across devices."""
    from repro.kernels import ops
    rows = NamedSharding(mesh4, P("shard"))
    rep = NamedSharding(mesh4, P())
    if mode == "fp32":
        disp = ops._sharded_kernel_dispatch("box_ball", 16, "l2", TQ, TN,
                                            mesh4)
        args = (_spec((TQ, D), jnp.float32, rep),
                _spec((16, 16384, D), jnp.float32, rows),
                _spec((16, 16384, 128), jnp.float32, rows),
                _spec((4, 128), jnp.float32, rep))
    else:
        mq = ops.quant_meta_rows(4)
        disp = ops._sharded_quant_dispatch("box", 64, "l2", TQ, TN, mesh4)
        args = (_spec((16, TQ, D), jnp.float32, rows),
                _spec((16, D, 16384), jnp.int8, rows),
                _spec((16, mq, 16384), jnp.float32, rows),
                _spec((4, mq), jnp.float32, rep),
                _spec((TQ,), jnp.float32, rep))
    text = _compiled(disp, *args).as_text()
    assert not re.search(r"all-gather|all-to-all", text)


def test_grouped_dispatch_compiles_on_mesh(mosaic, mesh4):
    """The grouped program over a 4-device "shard" mesh: each device runs
    the kernel on its resident rows, and only candidate lists move for
    the merge — no device holds more than its quarter of the block."""
    from repro.kernels import ops
    rows = NamedSharding(mesh4, P("shard"))
    rep = NamedSharding(mesh4, P())
    disp = ops._grouped_kernel_dispatch(
        "box_ball", 16, "l2", TQ, TN, ops._grouped_k_top(16, 16, 16384), 4,
        mesh4)
    c = _compiled(disp, _spec((TQ, D), jnp.float32, rep),
                  _spec((TQ, 4, 128), jnp.float32, rep),
                  _spec((TQ, 16), jnp.bool_, rep),
                  _spec((TQ,), jnp.int32, rep),
                  _spec((16, 16384, D), jnp.float32, rows),
                  _spec((16, 16384, 128), jnp.float32, rows),
                  _spec((16, 16384), jnp.int32, rows))
    quarter_block = 16 * 16384 * D * 4 // 4
    mem = c.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < quarter_block


def test_graph_traversal_compiles_on_mesh(mosaic, mesh4):
    """The stitched traversal over a mesh-partitioned bucket block: the
    gathers stay XLA ops and the beam-step kernel runs under shard_map."""
    from repro.kernels.graph_topk import _traverse
    rows = NamedSharding(mesh4, P("shard"))
    rep = NamedSharding(mesh4, P())
    c = _traverse.lower(
        _spec((8, D), jnp.float32, rep),
        _spec((16, 16384), jnp.int32, rows),
        _spec((16, 16384, 64), jnp.int32, rows),
        _spec((16, 16384, D), jnp.float32, rows),
        _spec((16, 16384, 128), jnp.float32, rows), None, None, None,
        _spec((4, 128), jnp.float32, rep), _spec((64,), jnp.int32, rep),
        k=10, ef=128, width=8, max_iters=256, kind="box_ball", metric="l2",
        m=4, quantized=False, use_pallas=True, mesh=mesh4).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("w_pad", [128, 256])
def test_rerank_compiles(one_chip, w_pad):
    """The int8 path's fp32 rerank at the Deep10M cell's widths: 64 padded
    query rows of d=96, one or two buckets' 40 over-fetched candidates per
    row in 128 or 256 slots, k=10: plain XLA, no Mosaic call."""
    from repro.kernels import ops
    compiled = jax.jit(ops._rerank_dispatch(10, "l2")).lower(
        _spec((TQ, 96), jnp.float32, one_chip),
        _spec((TQ, w_pad, 96), jnp.float32, one_chip),
        _spec((TQ, w_pad), jnp.bool_, one_chip)).compile()
    assert "tpu_custom_call" not in compiled.as_text()
