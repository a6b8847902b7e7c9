"""Shared helpers of the benchmark's CPU tests: the repository root on
the path, and small configurations that drive a whole run on the CPU."""
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# widths cut for a test run; the benchmark's cells use the published ones
SMALL = {
    "vit-mnist.train-ensemble": {
        "spec": dict(hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=2, intermediate_size=64,
                     particles=2),
        "traffic": dict(batch_size=8, batches_per_epoch=2)},
    "qwen1.5-0.5b.chat-steady": {
        "spec": dict(hidden_size=64, num_attention_heads=2,
                     num_key_value_heads=2, intermediate_size=128,
                     vocab_size=512, num_hidden_layers=2, particles=2,
                     serving=dict(page_size=16, num_pages=64, max_active=4,
                                  max_seq_pages=8)),
        "traffic": dict(rate_per_s=4.0,
                        prompt=dict(dist="lognormal", median=16, sigma=0.8,
                                    min=8, max=64),
                        output=dict(dist="lognormal", median=8, sigma=0.5,
                                    min=2, max=16),
                        warm_buckets=[8, 16, 32, 64], check_tokens=32)},
    "qwen1.5-0.5b.gen-backlog": {
        "spec": dict(hidden_size=64, num_attention_heads=2,
                     num_key_value_heads=2, intermediate_size=128,
                     vocab_size=512, num_hidden_layers=2, particles=2,
                     serving=dict(page_size=16, num_pages=64, max_active=4,
                                  max_seq_pages=8)),
        "traffic": dict(clients=8, pool=32,
                        prompt=dict(dist="uniform", min=8, max=16),
                        output=dict(dist="lognormal", median=12, sigma=0.5,
                                    min=4, max=24),
                        warm_buckets=[8, 16], check_tokens=32,
                        fill_steps=2)},
}


def run_small(workload, capsys, *extra, seed=2 ** 31 + 11, seconds="1",
              trace="0", limits=None):
    """Drive one small run on the CPU through the harness's entry; return
    (exit code, stdout lines). ``limits`` replaces some of the cell's
    correctness limits."""
    import copy
    from bench import run as bench_run
    from bench.core.registry import Resolved, benchmark
    overrides = copy.deepcopy(SMALL[workload])
    if limits:
        checks = copy.deepcopy(Resolved(benchmark(ROOT), workload,
                                        ROOT).limits["checks"])
        for name, lim in limits.items():
            checks[name]["limit"] = lim
        overrides["limits"] = {"checks": checks}
    capsys.readouterr()
    rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", seconds, "--trace", trace, *extra],
                        require_chip=False, overrides=overrides,
                        t_proc=time.perf_counter(), compile_cache=False)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, out


def emulate_high(monkeypatch):
    """The correctness control computes at matmul precision "high" (three
    bf16 passes), which a TPU honours and a CPU ignores. Emulate it on
    the CPU: while the effective precision is "high", every fp32 matmul
    through ``jnp.matmul`` / ``@`` becomes hi*hi + hi*lo + lo*hi of its
    operands' bf16 splits. Programs traced at another precision are
    untouched (the precision is part of JAX's jit cache key)."""
    import jax.numpy as jnp
    from jax._src import config
    from jax._src.lax import lax as lax_internal
    exact = lax_internal.dot_general

    def split(x):
        hi = x.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)

    def three_pass(lhs, rhs, *args, **kw):
        if config.default_matmul_precision.value != "high" or \
                jnp.result_type(lhs) != jnp.float32 or \
                jnp.result_type(rhs) != jnp.float32:
            return exact(lhs, rhs, *args, **kw)
        (lh, ll), (rh, rl) = split(lhs), split(rhs)
        return (exact(lh, rh, *args, **kw) + exact(lh, rl, *args, **kw)
                + exact(ll, rh, *args, **kw))

    monkeypatch.setattr(lax_internal, "dot_general", three_pass)
