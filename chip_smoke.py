#!/usr/bin/env python3
"""Push's particle train-and-serve path, end to end, on one TPU chip.

Every phase goes through the library's own entry points at the full
published width of its model; weights are random and seeded, nothing is
downloaded.

  train   vit-mnist (Push App. C.1: d=320, 16 layers, 8 heads, MLP 1280),
          8 particles, backend="compiled": DeepEnsemble, MultiSWAG and
          SteinVGD. Held-out loss must be finite and fall below its value
          at initialisation. The Pallas moments kernel is checked against
          jnp on one trained particle's moments.
  serve   ``serve(pd).predict`` over the trained MultiSWAG store (BMA mean
          checked against a per-particle reference) and its serve-time
          sampling (Pallas diag-std kernel, checked against jnp).
  decode  qwen1.5-0.5b (24 layers, d=1024, 16 heads, vocab 151,936),
          2 particles, ``serve_decode`` continuous batching over a paged KV
          pool with the Pallas paged kernel. The first decode step is
          checked against the same service with the jnp gather oracle, and
          speculative decoding must emit the same tokens as plain decoding.

``--chips 4`` runs only the particle-axis sharding check: DeepEnsemble and
SteinVGD on vit-mnist with the particle axis sharded over a 4-chip mesh,
and on the NEL backend over 4 devices, each against a one-device run of
the same seed.

Each phase prints one JSON line: seconds split into compile and run,
ProgramCache and persistent-cache counters, peak device bytes, and the
Pallas kernels found as ``tpu_custom_call`` in the compiled HLO of the
programs it ran. The last line is ``{"ok": true, "device": {...}}``; any
failed check raises, so the script then exits non-zero without it. It
refuses to run without a TPU.

Run:  python chip_smoke.py              # one chip: train, serve, decode
      python chip_smoke.py --chips 4    # four chips: sharding parity only
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.bdl import DeepEnsemble, MultiSWAG, SteinVGD  # noqa: E402
from repro.core import (ParticleModule, Placement,  # noqa: E402
                        PushDistribution)
from repro.data.loader import DataLoader  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.kernels import swag_moments  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_bench_mesh  # noqa: E402
from repro.models import api  # noqa: E402
from repro.optim import adam, sgd  # noqa: E402
from repro.runtime import global_cache, jit_program  # noqa: E402
from repro.serve import serve, serve_decode  # noqa: E402

# tolerances of the reference checks (all in fp32 with "highest" matmuls)
SERVE_TOL = 1e-4        # |BMA mean - per-particle reference|, probabilities
SWAG_TOL = 1e-6         # |diag-std kernel^2 - jnp^2|, relative to max sq
MOMENTS_TOL = 1e-6      # |moments kernel - jnp|, relative to max |value|
DECODE_TOL = 1e-4       # |first decode step logprob, kernel - oracle|, nats
SHARD_TOL = 1e-3        # |posterior_pred sharded/NEL - one device|, logits


class CheckFailed(RuntimeError):
    pass


def check(ok, what: str):
    if not ok:
        raise CheckFailed(what)


@contextlib.contextmanager
def f32_matmuls():
    """fp32 matmuls on every thread (serving compiles on its own threads,
    so the thread-local ``jax.default_matmul_precision`` would not reach
    them): reference checks then measure the code, not bf16 rounding."""
    prev = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        yield
    finally:
        jax.config.update("jax_default_matmul_precision", prev)


def live_rows(store, key: str, slots=None):
    """Live rows of a stacked store key, gathered once (``slots`` picks a
    subset of live positions)."""
    live = [store.slot_of(p) for p in store.pids]
    idx = jnp.asarray(live if slots is None else [live[i] for i in slots])
    return jax.tree.map(lambda x: x[idx], store.stacked(key))


def require_tpu(devices, chips: int = 1):
    """Refuse anything but ``chips`` or more TPU devices."""
    if not devices or devices[0].platform != "tpu":
        found = devices[0].platform if devices else "no device"
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {found}")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: needs {chips} TPU chips, "
                         f"found {len(devices)}")


# ---------------------------------------------------------------------------
# per-phase measurement
# ---------------------------------------------------------------------------

# compile seconds = wall time covered by tracing, lowering or XLA compile
# (persistent-cache reads included) on any thread: the union of the event
# intervals, so nested traces and compiles on parallel threads count once
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_COMPILE_SPANS = []
_COUNTERS = {"pcache_requests": 0, "pcache_hits": 0, "pcache_writes": 0}


def _on_duration(event, secs, **_):
    if event in _COMPILE_EVENTS:
        end = time.perf_counter()
        _COMPILE_SPANS.append((end - secs, end))


def _covered(t0: float, t1: float) -> float:
    """Seconds of [t0, t1] covered by compile spans."""
    total, reach = 0.0, t0
    for a, b in sorted(_COMPILE_SPANS):
        a, b = max(a, reach), min(b, t1)
        if b > a:
            total += b - a
            reach = b
    return total


def _on_event(event, **_):
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        _COUNTERS["pcache_requests"] += 1
    elif event == "/jax/compilation_cache/cache_hits":
        _COUNTERS["pcache_hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _COUNTERS["pcache_writes"] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)

_KERNEL_RE = re.compile(r'op_name="[^"]*?(?:vmap\()*([^/"()]+)\)*/pallas_call"')


def pallas_kernels(programs) -> list:
    """Names of the Pallas kernels compiled into ``programs`` as
    ``tpu_custom_call`` (interpreted kernels leave none)."""
    names = set()
    for prog in programs:
        if prog.abstract_args is None:
            continue
        text = prog.fn.lower(*prog.abstract_args).compile().as_text()
        for line in text.splitlines():
            if 'custom_call_target="tpu_custom_call"' in line:
                m = _KERNEL_RE.search(line)
                names.add(m.group(1) if m else "unnamed")
    return sorted(names)


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_phase(name: str, fn, *args, audit: bool = True, **kw):
    """Run one phase and print its line; returns what ``fn`` returns.
    ``audit=False`` skips the kernel listing, which recompiles every
    program the phase ran."""
    gc.collect()    # particle objects form cycles: free earlier phases' HBM
    cache = global_cache()
    seen = {id(p) for p in cache.programs()}
    c0, pc0 = dict(_COUNTERS), cache.snapshot_stats()
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    t1 = time.perf_counter()
    c1, pc1 = dict(_COUNTERS), cache.snapshot_stats()
    ran = [p for p in cache.programs() if id(p) not in seen]
    kernels = pallas_kernels(ran) if audit else None
    wall, compile_s = t1 - t0, _covered(t0, t1)
    print(json.dumps({
        "phase": name,
        "seconds": wall, "compile_s": compile_s, "run_s": wall - compile_s,
        "program_cache": {k: pc1[k] - pc0[k]
                          for k in ("hits", "misses", "cold_compiles")},
        "persistent_cache": {k: c1[k] - c0[k] for k in
                             ("pcache_requests", "pcache_hits",
                              "pcache_writes")},
        "peak_bytes_in_use": _peak_bytes(),
        "programs": sorted({p.name for p in ran}),
        "tpu_custom_call": kernels,
        "audit_s": time.perf_counter() - t1,   # the kernel listing
    }), flush=True)
    return out


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def model_module(cfg) -> ParticleModule:
    return ParticleModule(
        init=lambda rng: api.init_params(rng, cfg),
        loss=lambda p, b: api.loss_fn(p, b, cfg),
        forward=lambda p, b: api.forward(p, b, cfg)[0], cfg=cfg)


def _algorithms(swag_rank: int, svgd_lr: float):
    return (("ensemble", DeepEnsemble, dict(optimizer=adam(1e-3))),
            ("swag", MultiSWAG, dict(optimizer=adam(1e-3),
                                     max_rank=swag_rank)),
            ("svgd", SteinVGD, dict(lr=svgd_lr, lengthscale=-1.0)))


def phase_train(cfg, *, particles: int, epochs: int, batch_size: int,
                num_batches: int, swag_rank: int, svgd_lr: float,
                seed: int):
    """Train the three algorithms; returns the trained MultiSWAG, still
    open (the other two are closed here to free the chip's memory)."""
    module = model_module(cfg)
    heldout = next(iter(DataLoader(cfg, batch_size=batch_size,
                                   num_batches=1, seed=seed + 1)))
    eval_loss = jax.jit(jax.vmap(lambda p, b: module.loss(p, b)[0],
                                 in_axes=(0, None)))
    init = jax.vmap(module.init)(
        jax.random.split(jax.random.PRNGKey(seed), particles))
    loss0 = float(np.mean(np.asarray(eval_loss(init, heldout))))
    del init
    check(np.isfinite(loss0), f"held-out loss at init is {loss0}")
    trained = None
    for name, algo_cls, kw in _algorithms(swag_rank, svgd_lr):
        algo = algo_cls(module, seed=seed, backend="compiled")
        dl = DataLoader(cfg, batch_size=batch_size, num_batches=num_batches,
                        seed=seed)
        _, last = algo.bayes_infer(dl, epochs, num_particles=particles, **kw)
        after = np.asarray(eval_loss(live_rows(algo.store, "params"),
                                     heldout))
        print(f"train {name}: last-batch loss per particle "
              f"{np.round(last, 4).tolist()}; held-out loss {loss0:.4f} at "
              f"init -> {after.mean():.4f}", flush=True)
        check(len(last) == particles and np.all(np.isfinite(last)),
              f"{name}: non-finite training loss {last}")
        check(np.all(np.isfinite(after)), f"{name}: non-finite held-out loss")
        check(after.mean() < loss0, f"{name}: held-out loss did not fall "
              f"({loss0} -> {after.mean()})")
        if name == "swag":
            _check_swag_moments(algo, epochs)
            trained = algo
        else:
            algo.cleanup()
        del algo
        gc.collect()
    return trained


def _check_swag_moments(ms, epochs: int):
    """The collected moments are sane, and the moments kernel matches the
    jnp oracle on one particle's full-width flattened state."""
    from jax.flatten_util import ravel_pytree
    n_live = np.asarray(live_rows(ms.store, "swag")["n"])
    check(np.all(n_live == epochs),
          f"swag: {n_live} collections, want {epochs}")
    st = jax.tree.map(lambda x: x[0], live_rows(ms.store, "swag", [0]))
    mean, _ = ravel_pytree(st["mean"])
    sq, _ = ravel_pytree(st["sq_mean"])
    theta, _ = ravel_pytree(jax.tree.map(
        lambda x: x[0], live_rows(ms.store, "params", [0])))
    args = (mean, sq, theta, jnp.float32(epochs))
    # through the ProgramCache, so the phase's kernel listing covers it
    got = jit_program("swag_moments", ("swag_moments_check",),
                      swag_moments.moments_flat, args)(*args)
    want = jax.jit(kref.swag_moments)(*args)
    for g, w in zip(got, want):
        err = float(jnp.max(jnp.abs(g - w)) / (jnp.max(jnp.abs(w)) + 1e-30))
        check(err <= MOMENTS_TOL, f"swag moments kernel off by {err}")
    print(f"swag moments: {mean.size} params per particle, kernel matches "
          "jnp", flush=True)


def phase_serve(ms, cfg, *, requests: int, seed: int):
    """BMA serving over a trained MultiSWAG's live particles, then its
    serve-time sampling; closes ``ms``."""
    module = model_module(cfg)
    probe = next(iter(DataLoader(cfg, batch_size=requests, num_batches=1,
                                 seed=seed + 2)))
    images = probe["images"]
    with serve(ms, kind="classify", max_batch=requests) as svc:
        futs = [svc.predict_async({"images": images[i]})
                for i in range(requests)]
        preds = [f.result(600.0) for f in futs]
    fwd = jax.jit(module.forward)
    params = live_rows(ms.store, "params")
    n = jax.tree.leaves(params)[0].shape[0]
    ref = np.mean([np.asarray(jax.nn.softmax(fwd(
        jax.tree.map(lambda x: x[i], params), {"images": images}), -1))
        for i in range(n)], axis=0)
    for i, p in enumerate(preds):
        mean = np.asarray(p.mean)
        ent, mi = float(p.entropy), float(p.mutual_info)
        print(f"serve request {i}: argmax={int(mean.argmax())} "
              f"label={int(probe['labels'][i])} entropy={ent:.4f} "
              f"mutual_info={mi:.5f}", flush=True)
        check(mean.shape == (cfg.vocab_size,), f"BMA mean shape {mean.shape}")
        err = float(np.abs(mean - ref[i]).max())
        check(err <= SERVE_TOL, f"request {i}: BMA mean off reference by "
              f"{err}")
        check(0.0 <= ent <= np.log(cfg.vocab_size) + 1e-4
              and -1e-6 <= mi <= ent + 1e-6, f"request {i}: heads {ent}, {mi}")

    rng = jax.random.PRNGKey(seed)
    with ms.posterior_predictive(samples_per_particle=1, rng=rng,
                                 kind="classify",
                                 max_batch=requests) as svc:
        check(svc.engine.num_particles == n,
              f"swag serves {svc.engine.num_particles} samples, want {n}")
        futs = [svc.predict_async({"images": images[i]})
                for i in range(requests)]
        for i, f in enumerate(futs):
            p = f.result(600.0)
            mean = np.asarray(p.mean)
            print(f"serve swag-sampled request {i}: "
                  f"argmax={int(mean.argmax())} "
                  f"entropy={float(p.entropy):.4f} "
                  f"mutual_info={float(p.mutual_info):.5f}", flush=True)
            check(np.all(np.isfinite(mean))
                  and abs(float(mean.sum()) - 1.0) < 1e-4,
                  f"swag request {i}: BMA mean {mean}")
    _check_swag_diag_std(ms)
    ms.cleanup()


def _check_swag_diag_std(ms):
    """The serve-time diag-std kernel matches jnp on one particle's
    full-width moments."""
    from jax.flatten_util import ravel_pytree
    st = jax.tree.map(lambda x: x[0], live_rows(ms.store, "swag", [0]))
    mean, _ = ravel_pytree(st["mean"])
    sq, _ = ravel_pytree(st["sq_mean"])
    got = jax.jit(swag_moments.diag_std_flat)(mean, sq)
    want = jnp.sqrt(jnp.maximum(sq - mean * mean, 1e-30))
    # compared as variances: sq - mean^2 cancels, so rounding in either
    # form is on the scale of sq, not of the (much smaller) variance
    err = float(jnp.max(jnp.abs(got * got - want * want)) / jnp.max(sq))
    check(err <= SWAG_TOL, f"swag diag-std kernel off by {err}")


def phase_decode(cfg, *, particles: int, num_pages: int, page_size: int,
                 max_seq_pages: int, max_active: int, prompts, max_new: int,
                 seed: int):
    """Continuous-batching decode through three services over one store:
    Pallas paged kernel, jnp gather oracle, and speculative (kernel)."""
    bucket = max(len(p) for p in prompts)
    runs = (("kernel", dict(decode_kernel=True)),
            ("oracle", dict(decode_kernel=False)),
            ("speculative", dict(decode_kernel=True, speculative=True)))
    gens = {}
    with PushDistribution(model_module(cfg), num_devices=1,
                          seed=seed) as pd:
        for _ in range(particles):
            pd.p_create()
        for label, kw in runs:
            svc = serve_decode(pd, cfg, num_pages=num_pages,
                               page_size=page_size, max_active=max_active,
                               max_seq_pages=max_seq_pages,
                               warmup_buckets=(bucket,), **kw)
            try:
                handles = [svc.generate_async(p, max_new=max_new)
                           for p in prompts]
                gens[label] = [h.result(900.0) for h in handles]
                st = svc.stats()
            finally:
                svc.close()
            print(f"decode {label}: steps={st['steps']} "
                  f"prefills={st['prefills']} "
                  f"tokens={[g.tokens for g in gens[label]]}", flush=True)
    for i, (k, o, s) in enumerate(zip(gens["kernel"], gens["oracle"],
                                      gens["speculative"])):
        check(len(k.tokens) == max_new
              and all(0 <= t < cfg.vocab_size for t in k.tokens),
              f"request {i}: tokens {k.tokens}")
        check(np.all(np.isfinite(k.logprobs)) and max(k.logprobs) <= 0.0,
              f"request {i}: logprobs {k.logprobs}")
        # token 0 comes from prefill, token 1 from the first decode step
        check(k.tokens[0] == o.tokens[0], f"request {i}: prefill tokens "
              f"{k.tokens[0]} vs {o.tokens[0]}")
        err = abs(k.logprobs[1] - o.logprobs[1])
        print(f"decode request {i}: first decode step logprob "
              f"kernel={k.logprobs[1]:.6f} oracle={o.logprobs[1]:.6f} "
              f"entropy={k.entropy[1]:.4f} "
              f"mutual_info={k.mutual_info[1]:.6f}", flush=True)
        check(err <= DECODE_TOL, f"request {i}: paged kernel off the jnp "
              f"oracle by {err} nats at the first decode step")
        check(s.tokens == k.tokens, f"request {i}: speculative tokens "
              f"{s.tokens} differ from plain {k.tokens}")


def _particle_axis_devices(store, key: str = "params") -> set:
    """Device ids holding shards of the stacked ``key`` tree, after
    checking every leaf is sharded on its leading (particle) axis."""
    devs = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            store.stacked(key))[0]:
        if leaf.ndim == 0:
            continue
        spec = leaf.sharding.spec
        check(spec and spec[0] == "data",
              f"{key}{jax.tree_util.keystr(path)}: particle axis not "
              f"sharded ({spec})")
        devs |= {s.device.id for s in leaf.addressable_shards}
    return devs


def phase_sharded(cfg, *, n_devices: int, particles: int, epochs: int,
                  batch_size: int, num_batches: int, svgd_lr: float,
                  seed: int):
    """Particle axis sharded over ``n_devices`` (mesh placement) and the
    NEL backend over ``n_devices``, each against one device, same seed."""
    module = model_module(cfg)
    probe = next(iter(DataLoader(cfg, batch_size=batch_size, num_batches=1,
                                 seed=seed + 1)))
    mesh = make_bench_mesh(n_devices)
    variants = (("one_device", dict(backend="compiled")),
                ("sharded", dict(backend="compiled", placement=Placement(
                    mesh=mesh, particle_axis="data", mode="tp"))),
                ("nel", dict(backend="nel", num_devices=n_devices)))
    algos = (("ensemble", DeepEnsemble, dict(optimizer=sgd(0.05))),
             ("svgd", SteinVGD, dict(lr=svgd_lr, lengthscale=-1.0)))
    for name, algo_cls, kw in algos:
        preds = {}
        for label, ctor in variants:
            with algo_cls(module, seed=seed, **ctor) as algo:
                dl = DataLoader(cfg, batch_size=batch_size,
                                num_batches=num_batches, seed=seed)
                algo.bayes_infer(dl, epochs, num_particles=particles, **kw)
                preds[label] = np.asarray(algo.posterior_pred(probe))
                if label == "sharded":
                    devs = _particle_axis_devices(algo.store)
                    check(len(devs) == n_devices,
                          f"{name}: particle axis on devices {sorted(devs)}, "
                          f"want {n_devices}")
        check(np.all(np.isfinite(preds["one_device"])),
              f"{name}: non-finite posterior predictive")
        for label in ("sharded", "nel"):
            err = float(np.abs(preds[label] - preds["one_device"]).max())
            print(f"sharded {name}: {label} vs one device, max "
                  f"|posterior_pred| diff {err:.3e} over {n_devices} "
                  "devices", flush=True)
            check(err <= SHARD_TOL, f"{name}: {label} posterior predictive "
                  f"off one device by {err}")


# ---------------------------------------------------------------------------
# full-width configurations
# ---------------------------------------------------------------------------

VIT = dict(particles=8, epochs=3, batch_size=64, num_batches=40)


def _decode_prompts(cfg, seed: int, n: int = 3):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(1, cfg.vocab_size, int(L))))
            for L in rng.integers(5, 17, n)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the particle-axis sharding check")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args(argv)

    require_tpu(jax.devices(), a.chips)
    use_compile_cache(ROOT)     # before the first compile
    vit = configs.get("vit-mnist")
    if a.chips > 1:
        # no Pallas kernel runs on this path (the fused SVGD force is the
        # jnp form), and re-compiling the sharded SVGD step to list none
        # would cost minutes of four chips
        with f32_matmuls():
            run_phase("sharded", phase_sharded, vit, n_devices=a.chips,
                      audit=False, particles=VIT["particles"], epochs=2,
                      batch_size=VIT["batch_size"], num_batches=4,
                      svgd_lr=0.05, seed=a.seed)
    else:
        ms = run_phase("train", phase_train, vit, swag_rank=4, svgd_lr=0.05,
                       seed=a.seed, **VIT)
        with f32_matmuls():
            run_phase("serve", phase_serve, ms, vit, requests=4, seed=a.seed)
        del ms      # frees the trained store before the LM is built
        qwen = configs.get("qwen1.5-0.5b")
        with f32_matmuls():
            run_phase("decode", phase_decode, qwen, particles=2,
                      num_pages=256, page_size=16, max_seq_pages=4,
                      max_active=4, prompts=_decode_prompts(qwen, a.seed),
                      max_new=8, seed=a.seed)
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
