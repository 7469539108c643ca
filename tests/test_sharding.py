"""Sharding rule units: param pspecs (stacked/unstacked by rank), cache
pspecs, non-divisible fallbacks, and the logical-axis shard() constraint."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke_config
from repro.distributed.sharding import (
    cache_shardings,
    decode_rules,
    long_decode_rules,
    param_pspec,
    params_shardings,
    shard,
    train_rules,
    use_rules,
)
from repro.models import build_model


@pytest.fixture(scope="module")
def mesh():
    # 1 real device: a (1, 1) mesh — axis *names* drive pspec construction,
    # extent-1 axes make every dim "divisible" so rules resolve fully.
    from repro.launch.mesh import make_test_mesh

    return make_test_mesh((1, 1))


def test_param_pspec_stacked_by_rank(mesh):
    # (L, D, H, Dh) — stacked attention projection
    assert param_pspec("/seg0/0/mixer/wq", (4, 64, 8, 16), mesh) == P(
        None, "data", "model", None
    )
    # (D, H, Dh) — unstacked (repeats==1 segment or unrolled probe)
    assert param_pspec("/seg0/0/mixer/wq", (64, 8, 16), mesh) == P(
        "data", "model", None
    )


def test_param_pspec_norms_replicated(mesh):
    assert param_pspec("/seg0/0/ln1", (4, 64), mesh) == P(None, None)
    assert param_pspec("/final_norm", (64,), mesh) == P(None)


def test_param_pspec_embed_and_head(mesh):
    assert param_pspec("/embed", (1024, 64), mesh) == P("model", "data")
    assert param_pspec("/lm_head", (64, 1024), mesh) == P("data", "model")


def test_param_pspec_fsdp_disable(mesh):
    got = param_pspec("/seg0/0/mixer/wq", (64, 8, 16), mesh, fsdp_axis=None)
    assert got == P(None, "model", None)


def test_param_pspec_nondivisible_replicates():
    from repro.launch.mesh import make_test_mesh

    mesh2 = make_test_mesh((1, 1))
    # simulate extent via a fake mesh is moot at extent 1; use rank mismatch:
    # a rank the rules don't expect must fully replicate, never crash
    assert param_pspec("/seg0/0/mixer/wq", (3, 4, 64, 8, 16), mesh2) == P(
        None, None, None, None, None
    )


@pytest.mark.parametrize("arch", ["deepseek-v2-236b", "jamba-v0.1-52b"])
def test_params_shardings_cover_whole_tree(mesh, arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    sh = params_shardings(params, mesh)
    # same structure, every leaf a NamedSharding of matching rank
    jax.tree.map(
        lambda l, s: (_ for _ in ()).throw(AssertionError((l.shape, s.spec)))
        if len(s.spec) != l.ndim and len(s.spec) != 0
        else None,
        params,
        sh,
    )


def test_cache_shardings_stacked_vs_unstacked(mesh):
    cfg = get_smoke_config("deepseek-v2-236b")  # seg0 repeats=1 + seg1 stacked
    model = build_model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(4, 32, jnp.float32))
    sh = cache_shardings(cache, mesh)

    flat = jax.tree_util.tree_flatten_with_path(sh)[0]
    specs = {
        "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path): s.spec
        for path, s in flat
    }
    # unstacked first-layer MLA cache: (B, S, R) → batch, seq(model), none
    unstacked = [v for k, v in specs.items() if k.startswith("seg0") and "ckv" in k]
    stacked = [v for k, v in specs.items() if k.startswith("seg1") and "ckv" in k]
    assert unstacked and stacked
    assert unstacked[0][1] == "model" and len(unstacked[0]) == 3
    assert stacked[0][0] is None and stacked[0][2] == "model"  # stack dim first


def test_shard_constraint_drops_nondivisible(mesh):
    rules = train_rules(mesh)
    with use_rules(rules):
        x = jnp.zeros((2, 8, 16))
        y = shard(x, "batch", "seq", "embed")  # extent-1 axes: all divisible
        assert y.shape == x.shape
    # outside a rules context shard() is the identity
    z = shard(jnp.zeros((3,)), "batch")
    assert z.shape == (3,)


def test_rule_presets_differ_where_expected(mesh):
    tr = train_rules(mesh).logical
    dr = decode_rules(mesh).logical
    lr = long_decode_rules(mesh).logical
    assert tr["heads"] == "model" and dr["heads"] is None
    assert dr["kv_seq"] == "model" and lr["kv_seq"] == "data"
    assert tr["batch"] == ("data",) and lr["batch"] is None
