"""Counts from shapes (each configuration's reference), tied to the
published sizes and to the program's own parameter trees."""
import math

import jax
import pytest

from benchlib import ROOT

from bench.core import peaks
from bench.core.registry import Resolved, benchmark


@pytest.fixture(scope="module")
def refs():
    """Each configuration's (sizes, reference module)."""
    b = benchmark(ROOT)
    out = {}
    for w in b["workloads"]:
        r = Resolved(b, w["name"], ROOT)
        out[w["config"]] = (r.spec, r.reference)
    return out


def test_parameters_per_particle_are_the_published_counts(refs):
    v, q = refs["vit-mnist"], refs["qwen1.5-0.5b"]
    assert v[1].param_count(v[0]) == 19_775_360
    assert q[1].param_count(q[0]) == 463_987_712
    for spec, ref in refs.values():
        assert ref.param_count(spec) == spec["parameters_per_particle"]


def test_kv_bytes_per_token_per_particle(refs):
    q, ref = refs["qwen1.5-0.5b"]
    assert ref.kv_bytes_per_token(q) == 196_608 \
        == q["kv_bytes_per_token_per_particle"]


@pytest.mark.parametrize("name", ["vit-mnist", "qwen1.5-0.5b"])
def test_counts_match_the_program_tree(name):
    from repro.models import api
    b = benchmark(ROOT)
    w = next(w["name"] for w in b["workloads"] if w["config"] == name)
    r = Resolved(b, w, ROOT)
    cfg = r.reference.program_config(r.spec)
    tree = jax.eval_shape(lambda: api.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    n = sum(math.prod(x.shape) for x in jax.tree.leaves(tree))
    assert n == r.reference.param_count(r.spec)
    ref = jax.eval_shape(lambda: r.reference.init_params(
        jax.random.PRNGKey(0), r.spec))
    assert jax.tree.structure(ref) == jax.tree.structure(tree)
    assert [x.shape for x in jax.tree.leaves(ref)] == \
        [x.shape for x in jax.tree.leaves(tree)]


def test_flops_follow_the_shapes(refs):
    (v, vit), (q, qwen) = refs["vit-mnist"], refs["qwen1.5-0.5b"]
    # a ViT training step of 64 images is 3 forwards; the matmuls are
    # 2 FLOPs per parameter per row that meets them
    assert vit.train_flops(v, 64) == 3 * vit.forward_flops(v, 64)
    assert 1.2e12 < 32 * vit.train_flops(v, 64) < 1.25e12
    n = qwen.param_count(q)
    # a decoded token: 2 FLOPs per weight (the tied table once, as head)
    # plus attention, which grows with the context
    per_tok = qwen.decode_token_flops(q, 1)
    assert per_tok == pytest.approx(2 * n, rel=1e-3)
    assert qwen.decode_token_flops(q, 1001) - per_tok == \
        qwen.attn_flops(q, 1000)
    assert qwen.prefill_flops(q, 1) == per_tok
    f, b = qwen.paged_attn_cost(q, 1000, 1)
    assert b == 1000 * 196_608 + 24 * 2 * 16 * 64 * 4
    t, bound = peaks.roofline_seconds(f, b, peaks.peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(b / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError):
        peaks.peaks("cpu")
