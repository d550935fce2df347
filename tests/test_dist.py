"""repro.dist: sharding rules, logical-axis contexts, EP/TP MoE."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, reduced_config
from repro.dist import sharding as shd
from repro.dist.ctx import constrain, current, resolve, sharding_ctx
from repro.launch.mesh import auto_mesh
from repro.launch.specs import batch_sds, cache_sds, opt_sds, params_sds
from repro.optim import adamw


class FakeMesh:
    """Spec-rule tests against meshes larger than this host: the rules
    only read axis_names + devices.shape, so no devices are needed."""

    def __init__(self, shape, axes):
        self.devices = np.empty(shape, object)
        self.axis_names = axes


MESH_8 = FakeMesh((2, 4), ("data", "model"))
MESH_POD = FakeMesh((2, 4, 4), ("pod", "data", "model"))


def real_mesh():
    return auto_mesh((jax.device_count(), 1), ("data", "model"))


# ---------------------------------------------------------------------------
# param / opt / batch / cache specs
# ---------------------------------------------------------------------------

class TestParamSpecs:
    def setup_method(self, _):
        self.cfg = reduced_config(ARCHS["llama3.2-3b"])
        self.params = params_sds(self.cfg)

    def test_full_rank_and_stack_dim_unsharded(self):
        specs = shd.param_specs(self.params, MESH_8)
        flat_p = jax.tree_util.tree_flatten_with_path(self.params)[0]
        flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_p) == len(flat_s)
        for (path, leaf), spec in zip(flat_p, flat_s):
            assert len(spec) == leaf.ndim, (path, leaf.shape, spec)
            if any(getattr(k, "key", None) == "blocks" for k in path):
                assert spec[0] is None  # scanned layer stack stays whole

    def test_divisibility_respected(self):
        sizes = dict(zip(MESH_POD.axis_names, MESH_POD.devices.shape))
        specs = shd.param_specs(self.params, MESH_POD)
        for leaf, spec in zip(jax.tree.leaves(self.params),
                              jax.tree.leaves(
                                  specs, is_leaf=lambda x: isinstance(x, P))):
            for dim, entry in zip(leaf.shape, spec):
                if entry is None:
                    continue
                axes = entry if isinstance(entry, tuple) else (entry,)
                prod = int(np.prod([sizes[a] for a in axes]))
                assert dim % prod == 0, (leaf.shape, spec)

    def test_strategies(self):
        w = {"w": jax.ShapeDtypeStruct((256, 512), jnp.bfloat16)}
        assert shd.param_specs(w, MESH_8, "replicated")["w"] == P(None, None)
        tp = shd.param_specs(w, MESH_8, "tp_serve")["w"]
        assert "model" in tp and "data" not in tp
        fsdp = shd.param_specs(w, MESH_8, "fsdp")["w"]
        assert "model" in fsdp and "data" in fsdp
        with pytest.raises(ValueError, match="strategy"):
            shd.param_specs(w, MESH_8, "nope")

    def test_opt_specs_zero3(self):
        pspec = shd.param_specs(self.params, MESH_8)
        ospec = shd.opt_specs(opt_sds(self.cfg), pspec, MESH_8)
        assert isinstance(ospec, adamw.OptState)
        assert ospec.step == P()
        assert jax.tree.leaves(ospec.master,
                               is_leaf=lambda x: isinstance(x, P)) \
            == jax.tree.leaves(pspec, is_leaf=lambda x: isinstance(x, P))

    def test_batch_specs_divisibility(self):
        b = batch_sds(self.cfg, 8, 64)
        sp = shd.batch_specs(b, MESH_8)
        assert sp["tokens"] == P("data", None)       # 8 % 2 == 0
        b3 = batch_sds(self.cfg, 3, 64)
        assert shd.batch_specs(b3, MESH_8)["tokens"] == P(None, None)

    def test_cache_specs_kv_heads_on_model(self):
        cache = cache_sds(self.cfg, 8, 32)

        def kv_specs(mesh):
            flat = jax.tree_util.tree_flatten_with_path(
                shd.cache_specs(cache, mesh))[0]
            return [(p, s) for p, s in flat
                    if getattr(p[-1], "key", None) in ("k", "v")]

        # model axis 2 divides the 2 kv heads -> sharded
        kv = kv_specs(FakeMesh((4, 2), ("data", "model")))
        assert kv
        for path, spec in kv:
            assert spec[0] is None and spec[1] == "data"
            assert spec[len(spec) - 2] == "model", (path, spec)
        # model axis 4 does not divide 2 kv heads -> dropped, batch kept
        for path, spec in kv_specs(MESH_8):
            assert spec[1] == "data" and "model" not in spec, (path, spec)

    def test_to_named_real_mesh(self):
        mesh = real_mesh()
        sh = shd.to_named(shd.param_specs({"w": jnp.ones((4, 8))}, mesh),
                          mesh)
        assert isinstance(sh["w"], NamedSharding)
        placed = jax.device_put(jnp.ones((4, 8)), sh["w"])
        assert placed.sharding == sh["w"]


# ---------------------------------------------------------------------------
# logical-axis context
# ---------------------------------------------------------------------------

class TestCtx:
    def test_no_ctx_identity(self):
        x = jnp.ones((4, 8))
        assert current() is None
        assert constrain(x, ("dp", None)) is x    # strict no-op off-ctx

    def test_ctx_nesting_and_teardown(self):
        mesh = real_mesh()
        with sharding_ctx(mesh, dp_axes=("data",), tp_axis="model") as ctx:
            assert current() is ctx
            with sharding_ctx(mesh) as inner:
                assert current() is inner
            assert current() is ctx
        assert current() is None

    def test_ctx_teardown_on_error(self):
        mesh = real_mesh()
        with pytest.raises(RuntimeError):
            with sharding_ctx(mesh):
                raise RuntimeError("boom")
        assert current() is None

    def test_resolve_divisibility_drop(self):
        from repro.dist.ctx import ShardingCtx
        ctx = ShardingCtx(MESH_POD, ("pod", "data"), "model")
        # dp = 2*2=4 divides 8; tp = 4 does not divide 6 -> dropped
        assert resolve(ctx, (8, 6), ("dp", "tp")) == P(("pod", "data"), None)
        assert resolve(ctx, (8, 12), ("dp", "tp")) == P(("pod", "data"),
                                                        "model")
        # unknown mesh axis resolves to None instead of erroring
        assert resolve(ctx, (8,), ("ici",)) == P(None)

    def test_constrain_under_jit(self):
        mesh = real_mesh()

        def fn(x):
            with sharding_ctx(mesh, dp_axes=("data",), tp_axis="model"):
                return constrain(x, ("dp", "tp", None)) * 2

        x = jnp.ones((4, 8, 2))
        np.testing.assert_array_equal(np.asarray(jax.jit(fn)(x)),
                                      np.asarray(x) * 2)

    def test_constrain_rank_mismatch_raises(self):
        mesh = real_mesh()
        with sharding_ctx(mesh):
            with pytest.raises(ValueError, match="logical axes"):
                constrain(jnp.ones((2, 2)), ("dp",))


# ---------------------------------------------------------------------------
# expert-parallel MoE
# ---------------------------------------------------------------------------

class TestMoeEP:
    def _setup(self):
        from repro.models.lm import _init_moe
        cfg = reduced_config(ARCHS["mixtral-8x7b"])
        p = _init_moe(cfg, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model),
                              jnp.float32).astype(jnp.bfloat16)
        return cfg, p, x

    @pytest.mark.parametrize("impl_name", ["moe_ffn_tp", "moe_ffn_ep"])
    def test_matches_dense_reference(self, impl_name):
        from repro.dist import moe_ep
        from repro.models.moe import moe_ffn
        cfg, p, x = self._setup()
        kw = dict(n_experts=cfg.n_experts, top_k=cfg.top_k, cap_factor=4.0)
        ref, logits_ref, idx_ref = moe_ffn(p, x, **kw)
        mesh = real_mesh()
        with sharding_ctx(mesh, dp_axes=("data",), tp_axis="model"):
            out, logits, idx = getattr(moe_ep, impl_name)(p, x, **kw)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(logits_ref),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=2e-2, atol=2e-2)

    def test_shared_experts_arch(self):
        """qwen2-moe adds shared experts + sigmoid gate on both paths."""
        from repro.dist.moe_ep import moe_ffn_tp
        from repro.models.lm import _init_moe
        from repro.models.moe import moe_ffn
        cfg = reduced_config(ARCHS["qwen2-moe-a2.7b"])
        p = _init_moe(cfg, jax.random.PRNGKey(2))
        x = jax.random.normal(jax.random.PRNGKey(3), (32, cfg.d_model),
                              jnp.float32).astype(jnp.bfloat16)
        kw = dict(n_experts=cfg.n_experts, top_k=cfg.top_k, cap_factor=4.0)
        ref, _, _ = moe_ffn(p, x, **kw)
        with sharding_ctx(real_mesh(), dp_axes=("data",), tp_axis="model"):
            out, _, _ = moe_ffn_tp(p, x, **kw)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_fallback_without_ctx(self):
        from repro.dist.moe_ep import moe_ffn_ep, moe_ffn_tp
        from repro.models.moe import moe_ffn
        cfg, p, x = self._setup()
        kw = dict(n_experts=cfg.n_experts, top_k=cfg.top_k)
        ref, _, _ = moe_ffn(p, x, **kw)
        for impl in (moe_ffn_tp, moe_ffn_ep):
            out, _, _ = impl(p, x, **kw)
            np.testing.assert_allclose(np.asarray(out, np.float32),
                                       np.asarray(ref, np.float32),
                                       rtol=1e-5, atol=1e-5)


def test_lm_auto_selects_tp_moe_under_ctx():
    """The model picks the shard_map MoE when a ctx is active and the
    result matches the dense path run without one."""
    from repro.models import forward_train, init_params
    cfg = dataclasses.replace(reduced_config(ARCHS["mixtral-8x7b"]),
                              n_layers=2, layer_pattern=("attn",))
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    loss_plain, _ = forward_train(cfg, params, batch)
    mesh = real_mesh()

    def fn(p, b):
        with sharding_ctx(mesh, dp_axes=("data",), tp_axis="model"):
            return forward_train(cfg, p, b)

    loss_ctx, _ = jax.jit(fn)(params, batch)
    np.testing.assert_allclose(float(loss_ctx), float(loss_plain),
                               rtol=5e-2, atol=5e-2)
