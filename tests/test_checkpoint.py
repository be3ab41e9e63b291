"""Checkpointing: atomicity, integrity, elastic restore, data-order resume."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import DataConfig, SyntheticTokenPipeline
from repro.training.checkpoint import CheckpointManager


def _state(seed=0):
    k = jax.random.key(seed)
    return {"params": {"w": jax.random.normal(k, (16, 8)),
                       "b": jnp.zeros(8)},
            "opt": {"m": jnp.ones((16, 8)), "step": jnp.int32(7)}}


def test_save_restore_roundtrip(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    st = _state()
    cm.save(10, st, extra={"data_step": 10})
    restored, manifest = cm.restore(st)
    assert manifest["step"] == 10
    assert manifest["extra"]["data_step"] == 10
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_keeps_latest_and_gc(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2)
    st = _state()
    for s in (1, 2, 3, 4):
        cm.save(s, st)
    assert cm.available_steps() == [3, 4]


def test_corruption_falls_back(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=5)
    st = _state()
    cm.save(1, st)
    cm.save(2, st)
    # corrupt latest: flip bytes in one array file
    cdir = os.path.join(str(tmp_path), "step_00000002")
    manifest = json.load(open(os.path.join(cdir, "manifest.json")))
    victim = list(manifest["leaves"].values())[0]["file"]
    with open(os.path.join(cdir, victim), "r+b") as f:
        f.seek(200)
        f.write(b"\xde\xad\xbe\xef")
    restored, m = cm.restore(st)
    assert m["step"] == 1                         # fell back to valid step


def test_no_partial_checkpoint_visible(tmp_path):
    """A .tmp directory (simulated crash mid-save) is never restorable."""
    cm = CheckpointManager(str(tmp_path))
    st = _state()
    cm.save(5, st)
    os.makedirs(os.path.join(str(tmp_path), "step_00000009.tmp"))
    assert cm.available_steps() == [5]


def test_elastic_restore_resharded(tmp_path):
    """Restore onto a different sharding (device count change simulated by a
    different PartitionSpec on one device)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    cm = CheckpointManager(str(tmp_path))
    st = _state()
    cm.save(3, st)
    from jax.sharding import AxisType
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    sh = jax.tree.map(lambda a: NamedSharding(mesh, P()), st)
    restored, _ = cm.restore(st, shardings=sh)
    assert restored["params"]["w"].sharding == NamedSharding(mesh, P())


def test_data_resume_bit_identical():
    """The stateless pipeline regenerates identical batches from a cursor."""
    cfg = DataConfig(vocab=97, seq_len=16, global_batch=4, seed=3)
    p1 = SyntheticTokenPipeline(cfg)
    ref = [p1.batch(s) for s in range(10)]
    p2 = SyntheticTokenPipeline(cfg)              # "restarted job"
    for s in (5, 6, 9):
        np.testing.assert_array_equal(p2.batch(s)["tokens"],
                                      ref[s]["tokens"])


def test_host_sharded_pipeline_partitions():
    """n_hosts shards partition the global batch without overlap."""
    full = SyntheticTokenPipeline(DataConfig(vocab=31, seq_len=8,
                                             global_batch=8, seed=4))
    parts = [SyntheticTokenPipeline(DataConfig(vocab=31, seq_len=8,
                                               global_batch=8, seed=4,
                                               n_hosts=4, host_id=h))
             for h in range(4)]
    want = full.batch(2)["tokens"]
    got = np.concatenate([p.batch(2)["tokens"] for p in parts], axis=0)
    np.testing.assert_array_equal(want, got)
