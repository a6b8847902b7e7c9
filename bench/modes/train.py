"""Training mode: particles trained through the algorithm's fused epoch
path, as users of ``backend="compiled"`` train them.

Set-up builds one algorithm object from weights the configuration's
reference makes from the seed, and drives its fused epoch call
(``_fused_epochs``: store checkout, the donated compiled step over every
batch of the feed, commit, loss sync) for the first ``checked_steps``
steps, one batch per call, on the program's seeded DataLoader. After
step 1 it reads the first gradient back from Adam's first moment (m / (1
- b1)); after the last it reads the parameters' change. The same object
then runs whole epochs, call after call, until the window has lasted
``--seconds``: the rate is all samples over all that time.

Once the window has closed and the program's state is freed, the
reference follows the checked steps from the same weights and batches,
and three numbers are compared (see ``compare``).
"""
from __future__ import annotations

import gc
import time

import numpy as np

STEP_LEAF_FLOOR = 1e-3   # leaves whose reference gradient is below this
#                          share of the median leaf's move by round-off
#                          alone under Adam, and are left out of step_gap


class Feed:
    """The program's DataLoader, each ``next`` timed; ``limit`` cuts an
    epoch short (the checked steps), ``keep`` batches are kept for the
    reference."""

    def __init__(self, loader, cell, keep: int):
        self.loader, self.cell, self.keep = loader, cell, keep
        self.limit = None
        self.kept = []
        self.times = []

    def __iter__(self):
        it = iter(self.loader)
        n = 0
        while self.limit is None or n < self.limit:
            t0 = time.perf_counter()
            try:
                batch = next(it)
            except StopIteration:
                return
            t1 = time.perf_counter()
            self.times.append((t0, t1))
            if self.cell.trace:
                self.cell.host_spans.append(("data.loader", t0, t1))
            if len(self.kept) < self.keep:
                self.kept.append(batch)
            n += 1
            yield batch


def leaf_norms(tree):
    """(P, leaves) L2 norms of a tree with a leading particle axis."""
    import jax
    import jax.numpy as jnp
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        x.reshape(x.shape[0], -1).astype(jnp.float32)), axis=1))
        for x in jax.tree.leaves(tree)], axis=1)


def norm_gap(prog, ref, keep=None) -> float:
    """Worst leaf's |norm gap|, each against the larger of its reference
    norm and its particle's median leaf norm."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    denom = np.maximum(ref, np.median(ref, axis=1, keepdims=True))
    gap = np.abs(prog - ref) / np.maximum(denom, 1e-30)
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    return float(gap.max())


def run(cell):
    import jax
    import jax.numpy as jnp
    from repro.bdl import DeepEnsemble
    from repro.core import ParticleModule
    from repro.data.loader import DataLoader
    from repro.models import api
    from repro.optim import adam

    from bench.core import refops

    spec, tr, ref = cell.spec, cell.traffic, cell.r.reference
    if tr["algorithm"] != "DeepEnsemble":
        raise ValueError(f"train mode runs DeepEnsemble, not "
                         f"{tr['algorithm']!r}")
    cfg = ref.program_config(spec)
    n_p, bsz = int(spec["particles"]), int(tr["batch_size"])
    o = tr["optimizer"]
    opt = adam(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"])
    module = ParticleModule(init=None,
                            loss=lambda p, b: api.loss_fn(p, b, cfg),
                            forward=lambda p, b: api.forward(p, b, cfg)[0],
                            cfg=cfg)
    if cell.control:     # the control: the program one precision below
        jax.config.update("jax_default_matmul_precision", cell.control)
    algo = DeepEnsemble(module, seed=cell.seed, backend="compiled")

    init_one = jax.jit(lambda k: ref.init_params(k, spec))
    keys = refops.particle_keys(cell.seed, n_p)
    want = jax.tree.structure(jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0), cfg)))
    got = jax.tree.structure(jax.eval_shape(init_one, keys[0]))
    if want != got:
        raise ValueError(f"reference weights do not fit the program's "
                         f"tree: {got} vs {want}")
    pids = [algo.push_dist.p_create(opt, params=init_one(k)) for k in keys]
    jax.block_until_ready(algo.store.stacked("params"))
    cell.mark("weights")

    n_check = int(tr["checked_steps"])
    feed = Feed(DataLoader(cfg, batch_size=bsz,
                           num_batches=int(tr["batches_per_epoch"]),
                           seed=cell.seed), cell, keep=n_check)
    slots = np.asarray([algo.store.slot_of(p) for p in pids])
    b1 = o["b1"]
    norms = jax.jit(leaf_norms)
    losses, g_norms, d_norms = [], None, None
    feed.limit = 1
    for step in range(n_check):
        losses.append(algo._fused_epochs(pids, feed, 1, optimizer=opt))
        if step == 0:
            m = algo.store.stacked("opt_state")["m"]
            g_norms = np.asarray(norms(m))[slots] / (1.0 - b1)
    params = algo.store.stacked("params")
    delta = jax.jit(lambda a, k, i: leaf_norms(jax.tree.map(
        lambda x, y: (x[i] - y)[None], a, init_one(k))))
    d_norms = np.concatenate([np.asarray(delta(params, k, s))
                              for k, s in zip(keys, slots)])
    del params
    feed.limit = None

    cell.start_window()
    steps = 0
    last = None
    while True:
        last = algo._fused_epochs(pids, feed, 1, optimizer=opt)
        steps += int(tr["batches_per_epoch"])
        if time.perf_counter() - cell.t_w0 >= cell.seconds:
            break
    cell.end_window()
    cell.read_memory()

    cell.attempted = steps
    cell.failed = 0 if last and np.all(np.isfinite(last)) else steps
    cell.e2e["train_samples_per_s"] = n_p * bsz * steps / cell.window_s
    cell.layer.update(steps=steps, particles=n_p, batch=bsz,
                      loader_times=[t for t in feed.times
                                    if t[0] >= cell.t_w0])
    algo.cleanup()
    del algo, module
    gc.collect()
    cell.note_memory("bytes_in_use_before_reference")

    r = reference(ref, spec, o, keys, feed.kept, n_check)
    prog_loss = np.asarray(losses, np.float64).T          # (P, steps)
    rel = np.abs(prog_loss - r["loss"]) / np.abs(r["loss"])
    cell.notes["loss_gap_by_step"] = rel.max(axis=0).tolist()
    cell.check("loss_gap", float(rel.max()))
    cell.check("grad_gap", norm_gap(g_norms, r["grad"]))
    med = np.median(r["grad"], axis=1, keepdims=True)
    keep = r["grad"] >= STEP_LEAF_FLOOR * med
    cell.notes["step_gap_leaves_left_out"] = int(keep.size - keep.sum())
    cell.check("step_gap", norm_gap(d_norms, r["step"], keep=keep))


def reference(ref, spec, opt, keys, batches, n_steps, block: int = 4):
    """Losses, first-gradient leaf norms and change leaf norms of the
    plain reference, particles in blocks of ``block``, "highest"
    matmuls."""
    import jax
    import jax.numpy as jnp

    def run_block(ks):
        p0 = jax.vmap(lambda k: ref.init_params(k, spec))(ks)
        vg = jax.vmap(jax.value_and_grad(lambda p, b: ref.loss(p, b, spec)),
                      in_axes=(0, None))
        p = p0
        m = jax.tree.map(jnp.zeros_like, p0)
        v = jax.tree.map(jnp.zeros_like, p0)
        losses, g1 = [], None
        for s in range(n_steps):
            loss, g = vg(p, batches[s])
            losses.append(loss)
            if s == 0:
                g1 = leaf_norms(g)
            p, m, v = ref.adam_update(p, g, m, v, s + 1, opt)
        step = leaf_norms(jax.tree.map(lambda a, b: a - b, p, p0))
        return jnp.stack(losses, axis=1), g1, step

    with jax.default_matmul_precision("highest"):
        fn = jax.jit(run_block)
        out = {"loss": [], "grad": [], "step": []}
        for i in range(0, len(keys), block):
            ks = jnp.stack(keys[i:i + block])
            if ks.shape[0] < block:     # one shape: pad, then drop
                ks = jnp.concatenate([ks, jnp.repeat(ks[-1:], block
                                                     - ks.shape[0], 0)])
            loss, g1, st = (np.asarray(x, np.float64) for x in fn(ks))
            n = min(block, len(keys) - i)
            out["loss"].append(loss[:n])
            out["grad"].append(g1[:n])
            out["step"].append(st[:n])
    return {k: np.concatenate(v) for k, v in out.items()}
