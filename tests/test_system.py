"""End-to-end behaviour tests: the paper's three BDL algorithms train real
(reduced) models through the particle runtime, and the dry-run launcher
lowers + compiles against the production mesh in a subprocess."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.bdl import DeepEnsemble, MultiSWAG, SteinVGD
from repro.core import ParticleModule
from repro.data.loader import DataLoader
from repro.models import api
from repro.optim import adam, sgd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _vit_module():
    cfg = configs.get("vit-mnist").smoke().replace(n_units=2)
    return ParticleModule(
        init=lambda rng: api.init_params(rng, cfg),
        loss=lambda p, b: api.loss_fn(p, b, cfg),
        forward=lambda p, b: api.forward(p, b, cfg)[0],
        cfg=cfg), cfg


def _loader(cfg, n=3):
    dl = DataLoader(cfg, batch_size=8, num_batches=n, seed=0)
    return [jax.tree.map(jnp.asarray, b) for b in dl]


def test_deep_ensemble_end_to_end():
    mod, cfg = _vit_module()
    data = _loader(cfg)
    with DeepEnsemble(mod, num_devices=1) as de:
        pids, losses = de.bayes_infer(data, epochs=3, optimizer=adam(1e-3),
                                      num_particles=3)
        assert len(losses) == 3
        assert all(np.isfinite(l) for l in losses)
        pred = de.posterior_pred(data[0])
        assert pred.shape == (8, cfg.vocab_size)


def test_multiswag_end_to_end():
    mod, cfg = _vit_module()
    data = _loader(cfg)
    with MultiSWAG(mod, num_devices=1) as ms:
        pids, _ = ms.bayes_infer(data, epochs=3, optimizer=adam(1e-3),
                                 num_particles=2, pretrain_epochs=1, max_rank=4)
        for pid in pids:
            assert int(ms.push_dist.particles[pid].state["swag"]["rank"]) == 2
        pred = ms.sample_predict(data[0], samples_per_particle=2)
        assert pred.shape == (8, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(pred)))


def test_svgd_end_to_end():
    mod, cfg = _vit_module()
    data = _loader(cfg, n=2)
    with SteinVGD(mod, num_devices=1) as sv:
        pids, losses = sv.bayes_infer(data, epochs=5, num_particles=3,
                                      lengthscale=-1.0, lr=2e-3)
        assert len(pids) == 3
        assert all(np.isfinite(l) for l in losses)
        # particles stay distinct (repulsion, distinct inits)
        w = [jax.flatten_util.ravel_pytree(sv.push_dist.p_params(p))[0]
             for p in pids]
        assert float(jnp.abs(w[0] - w[1]).max()) > 1e-4


def test_training_reduces_loss_lm():
    """Compiled-path ensemble training on a tiny LM actually learns."""
    from repro.core import functional
    cfg = configs.get("qwen1.5-0.5b").smoke().replace(n_units=2)
    mod = ParticleModule(
        init=lambda rng: api.init_params(rng, cfg),
        loss=lambda p, b: api.loss_fn(p, b, cfg),
        forward=lambda p, b: api.forward(p, b, cfg)[0], cfg=cfg)
    data = [jax.tree.map(jnp.asarray, b) for b in
            DataLoader(cfg, batch_size=4, seq_len=32, num_batches=4, seed=1)]
    stacked = functional.init_stacked(mod, 2, jax.random.PRNGKey(0))
    opt = adam(3e-3)
    opt_state = jax.vmap(opt.init)(stacked)
    step = jax.jit(functional.ensemble_step(mod.loss, opt))
    first = None
    for epoch in range(6):
        for b in data:
            stacked, opt_state, losses = step(stacked, opt_state, b)
            if first is None:
                first = float(losses.mean())
    last = float(losses.mean())
    assert last < first * 0.9, (first, last)


@pytest.mark.slow
def test_dryrun_subprocess_production_mesh(tmp_path):
    """Deliverable (e) check: lower+compile on the 16x16 production mesh in a
    fresh process (512 forced host devices)."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen1.5-0.5b",
         "--shape", "decode_32k", "--mesh", "single", "--out",
         str(tmp_path)],
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
             "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.load(open(tmp_path / "qwen1.5-0.5b__decode_32k__single.json"))
    assert rec["status"] == "ok", rec
    assert rec["flops_per_device"] > 0
