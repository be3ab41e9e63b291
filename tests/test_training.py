"""Training substrate: loss decreases, schedules, optimizer, compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticTokenPipeline
from repro.models import build_model, init_params
from repro.training.compression import (compress_residual, dequantize_int8,
                                        init_error_state, quantize_int8)
from repro.training.optimizer import (OptConfig, global_norm, init_opt_state,
                                      schedule_lr)
from repro.training.train_step import init_train_state, make_train_step


def test_loss_decreases_end_to_end():
    """2-layer model on learnable synthetic data: loss must drop."""
    cfg = get_config("codeqwen1.5-7b", smoke=True)
    model = build_model(cfg)
    params = init_params(model.param_specs(), jax.random.key(0))
    state = init_train_state(params)
    pipe = SyntheticTokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=32,
                                             global_batch=8, seed=1))
    step = jax.jit(make_train_step(model, OptConfig(
        lr=3e-3, warmup_steps=5, total_steps=60, schedule="cosine")))
    losses = []
    for i in range(45):
        batch = jax.tree.map(jnp.asarray, pipe.batch(i))
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.9, losses[::10]
    assert np.isfinite(losses).all()


def test_grad_accum_matches_full_batch():
    """accum_steps=4 produces (nearly) the same update as accum_steps=1."""
    cfg = get_config("minicpm-2b", smoke=True)
    model = build_model(cfg)
    params = init_params(model.param_specs(), jax.random.key(0))
    pipe = SyntheticTokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=16,
                                             global_batch=8, seed=2))
    batch = jax.tree.map(jnp.asarray, pipe.batch(0))
    oc = OptConfig(lr=1e-3, warmup_steps=0, total_steps=10, schedule="const")
    s1, m1 = jax.jit(make_train_step(model, oc, 1))(init_train_state(params), batch)
    s4, m4 = jax.jit(make_train_step(model, oc, 4))(init_train_state(params), batch)
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 2e-2
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))),
        s1["params"], s4["params"])
    assert max(jax.tree.leaves(d)) < 5e-2


@pytest.mark.parametrize("sched", ["cosine", "wsd", "const"])
def test_schedules(sched):
    cfg = OptConfig(lr=1e-3, warmup_steps=10, total_steps=100, schedule=sched)
    lrs = [float(schedule_lr(jnp.int32(s), cfg)) for s in range(0, 101, 5)]
    assert lrs[0] < cfg.lr                       # warmup
    assert max(lrs) <= cfg.lr + 1e-9
    if sched in ("cosine", "wsd"):
        assert lrs[-1] < 0.35 * cfg.lr           # decayed at the end
    if sched == "wsd":
        # stable phase: flat in the middle
        mid = lrs[4:16]
        assert max(mid) - min(mid) < 1e-9


def test_grad_clip():
    from repro.training.optimizer import clip_by_global_norm
    g = {"a": jnp.ones((10,)) * 100.0}
    clipped, gn = clip_by_global_norm(g, 1.0)
    assert float(gn) > 100
    assert abs(float(global_norm(clipped)) - 1.0) < 1e-5


# ---------------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 10_000), scale=st.floats(1e-4, 1e3))
def test_quantize_roundtrip_bounded_error(seed, scale):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=(64,)) * scale, jnp.float32)
    q, s = quantize_int8(g)
    err = np.abs(np.asarray(dequantize_int8(q, s) - g))
    assert err.max() <= float(s) / 2 + 1e-6      # half-step quantization error


def test_error_feedback_accumulates():
    """Residual carries exactly the quantization error."""
    g = jnp.asarray([0.013, -0.5, 0.251], jnp.float32)
    q, s, resid = compress_residual(g)
    np.testing.assert_allclose(np.asarray(dequantize_int8(q, s) + resid),
                               np.asarray(g), rtol=1e-6)


def test_compressed_psum_shardmap():
    """Compressed all-reduce inside shard_map equals the plain mean (within
    int8 quantization error), error feedback shrinks the bias over steps."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax.experimental.shard_map import shard_map
    from repro.training.compression import compressed_psum
    from jax.sharding import AxisType
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(32,)),
                          jnp.float32)}
    e = init_error_state(g)

    def f(g, e):
        return compressed_psum(g, e, "data")

    out, new_e = shard_map(f, mesh=mesh, in_specs=(P(), P()),
                           out_specs=(P(), P()))(g, e)
    err = np.abs(np.asarray(out["w"] - g["w"]))
    assert err.max() < float(jnp.max(jnp.abs(g["w"]))) / 127 + 1e-6
    np.testing.assert_allclose(np.asarray(out["w"] + new_e["w"]),
                               np.asarray(g["w"]), rtol=1e-5, atol=1e-7)
