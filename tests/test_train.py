"""Training-loop numerics: loss decreases, optimizer behaves, elastic
re-meshing preserves training, serve path produces sane samples."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.data.pipeline import DataConfig, DataPipeline
from repro.models import init_model
from repro.models.config import ModelConfig
from repro.train.optimizer import OptConfig, apply_updates, init_opt, schedule
from repro.train.steps import TrainHyper, cross_entropy, loss_fn

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                   vocab_size=256, dtype="float32", param_dtype="float32",
                   remat=False)


def test_cross_entropy_matches_naive():
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (2, 8, 40))
    labels = jax.random.randint(key, (2, 8), 0, 32)
    got = cross_entropy(logits, labels, vocab=32, z_coef=0.0)
    lp = jax.nn.log_softmax(
        jnp.where(jnp.arange(40)[None, None] >= 32, -1e30, logits), -1)
    want = -jnp.take_along_axis(lp, labels[..., None], -1).mean()
    assert float(got) == pytest.approx(float(want), rel=1e-3)


def test_schedule_warmup_cosine():
    cfg = OptConfig(lr=1e-3, warmup_steps=10, total_steps=100)
    assert float(schedule(cfg, jnp.asarray(0))) == pytest.approx(0.0)
    assert float(schedule(cfg, jnp.asarray(10))) == pytest.approx(1e-3, rel=1e-5)
    assert float(schedule(cfg, jnp.asarray(100))) == pytest.approx(
        1e-4, rel=1e-3)


def test_loss_decreases_tiny_model():
    cfg = TINY
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8,
                      seed=0, n_chunks=64)
    pipeline = DataPipeline(data)
    params = init_model(jax.random.PRNGKey(0), cfg)
    opt_cfg = OptConfig(lr=3e-3, warmup_steps=5, total_steps=60,
                        weight_decay=0.0)
    opt = init_opt(params, opt_cfg)
    hyper = TrainHyper()

    @jax.jit
    def step(params, opt, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, cfg, batch, hyper)
        params, opt, om = apply_updates(params, grads, opt, opt_cfg)
        return params, opt, metrics["ce"]

    losses = []
    for _ in range(40):
        b = pipeline.next_batch()
        params, opt, ce = step(params, opt,
                               {k: jnp.asarray(v) for k, v in b.items()})
        losses.append(float(ce))
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    assert last < first * 0.8, (first, last)


def test_grad_compress_training_still_converges():
    cfg = TINY
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8,
                      seed=0, n_chunks=64)
    pipeline = DataPipeline(data)
    params = init_model(jax.random.PRNGKey(0), cfg)
    opt_cfg = OptConfig(lr=3e-3, warmup_steps=5, total_steps=60,
                        weight_decay=0.0)
    opt = init_opt(params, opt_cfg)
    hyper = TrainHyper(grad_compress=True)
    from repro.train.compress import compress_grads

    @jax.jit
    def step(params, opt, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, cfg, batch, hyper)
        grads = compress_grads(grads)
        params, opt, om = apply_updates(params, grads, opt, opt_cfg)
        return params, opt, metrics["ce"]

    losses = []
    for _ in range(40):
        b = pipeline.next_batch()
        params, opt, ce = step(params, opt,
                               {k: jnp.asarray(v) for k, v in b.items()})
        losses.append(float(ce))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.85


def test_elastic_trainer_changes_width(tmp_path):
    """ElasticTrainer follows a worker-count plan and keeps improving."""
    import numpy as np
    from repro.runtime.elastic import ElasticTrainer, SlotPlan

    cfg = TINY
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8,
                      seed=0, n_chunks=64)
    opt_cfg = OptConfig(lr=3e-3, warmup_steps=5, total_steps=100,
                        weight_decay=0.0)

    def make_step(mesh):
        from repro.train.steps import make_train_step
        fn, in_sh, out_sh = make_train_step(cfg, mesh, opt_cfg)
        jfn = jax.jit(fn)
        def wrapped(params, opt, batch):
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            return jfn(params, opt, batch)
        return wrapped, in_sh[0], in_sh[1]

    params = init_model(jax.random.PRNGKey(0), cfg)
    opt = None
    from repro.train.optimizer import init_opt as _io
    opt = _io(params, opt_cfg)
    trainer = ElasticTrainer(cfg, opt_cfg, data, str(tmp_path), make_step,
                             steps_per_slot=10)
    plan = [SlotPlan(0, 4), SlotPlan(1, 8), SlotPlan(2, 2)]
    out = trainer.run(plan, params, opt)
    assert out["steps"] == 30
    ces = [m["ce"] for m in trainer.metrics_log]
    assert np.mean(ces[-5:]) < np.mean(ces[:5])
    assert len(trainer.mesh_history) == 3


def test_grad_accum_matches_single_step():
    """Microbatched gradient accumulation == single-shot step (bitwise-
    tight in fp32): same params, same metrics, any k dividing the batch."""
    import jax
    from repro.launch.mesh import make_mesh
    from repro.train.steps import make_train_step
    cfg = TINY
    mesh = make_mesh((1, 1), ("data", "model"))
    opt_cfg = OptConfig(lr=1e-3)
    params = init_model(jax.random.PRNGKey(0), cfg)
    opt = init_opt(params, opt_cfg)
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
             "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab_size)}
    ref = None
    for k in (1, 2, 4):
        fn, _, _ = make_train_step(cfg, mesh, opt_cfg, TrainHyper(grad_accum=k))
        p2, _, m = jax.jit(fn)(params, opt, batch)
        leaf = jax.tree_util.tree_leaves(p2)[0]
        if ref is None:
            ref = (leaf, float(m["ce"]))
        else:
            assert float(m["ce"]) == pytest.approx(ref[1], rel=1e-6)
            assert float(jnp.max(jnp.abs(leaf - ref[0]))) < 1e-5
