"""Train a ~100M-param dense LM for a few hundred steps on the synthetic
learnable stream, with checkpoint/restart fault tolerance.

    PYTHONPATH=src python examples/train_100m.py [--steps 300]

(The full driver with mesh/sharding lives in repro.launch.train; this
example keeps a visible loss curve on one CPU device. A ~100M config is
d_model=512, 12 layers, vocab 32k — adjust down with --tiny if slow.)
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.data.pipeline import DataConfig, SyntheticTokenPipeline
from repro.models import build_model, init_params
from repro.models.common import ArchConfig
from repro.training.checkpoint import CheckpointManager
from repro.training.optimizer import OptConfig
from repro.training.train_step import init_train_state, make_train_step

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=300)
ap.add_argument("--tiny", action="store_true",
                help="4-layer 128-wide variant (fast CPU demo)")
ap.add_argument("--ckpt-dir", default="/tmp/repro_train100m")
args = ap.parse_args()

if args.tiny:
    cfg = ArchConfig(name="demo-8m", family="dense", n_layers=4, d_model=128,
                     n_heads=4, n_kv=4, d_ff=512, vocab=4096, remat=False)
else:
    cfg = ArchConfig(name="demo-100m", family="dense", n_layers=12,
                     d_model=512, n_heads=8, n_kv=8, d_ff=2048, vocab=32768,
                     remat=False)

model = build_model(cfg)
params = init_params(model.param_specs(), jax.random.key(0))
n = sum(p.size for p in jax.tree.leaves(params))
print(f"{cfg.name}: {n/1e6:.1f}M params")

state = init_train_state(params)
opt = OptConfig(lr=3e-3, warmup_steps=20, total_steps=args.steps,
                schedule="wsd")
step_fn = jax.jit(make_train_step(model, opt))
pipe = SyntheticTokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=64,
                                         global_batch=8, seed=0))
cm = CheckpointManager(args.ckpt_dir)

restored, manifest = cm.restore(state)
start = 0
if restored is not None:
    state = jax.tree.map(jnp.asarray, restored)
    start = manifest["extra"]["data_step"]
    print(f"resumed from step {start}")

t0 = time.time()
for i in range(start, args.steps):
    state, m = step_fn(state, jax.tree.map(jnp.asarray, pipe.batch(i)))
    if i % 25 == 0 or i == args.steps - 1:
        print(f"step {i:4d} loss {float(m['loss']):.4f} "
              f"lr {float(m['lr']):.2e}", flush=True)
    if i and i % 100 == 0:
        cm.save(i, state, extra={"data_step": i + 1})
print(f"trained {args.steps - start} steps in {time.time()-t0:.0f}s; "
      "loss should approach 0 on the learnable stream")
