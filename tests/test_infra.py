"""Infrastructure tests: hlo_cost parser, roofline terms, sharding rules,
specs, checkpointing, data loading."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_cost, roofline


class TestHloCost:
    def test_single_matmul_flops(self):
        x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
        txt = jax.jit(lambda a, b: a @ b).lower(x, x).compile().as_text()
        c = hlo_cost.analyze(txt)
        assert c.flops == pytest.approx(2 * 256 ** 3, rel=0.01)

    def test_scan_multiplies_by_trip_count(self):
        def scanned(ws, x):
            def body(c, w):
                return w @ c, None
            out, _ = jax.lax.scan(body, x, ws)
            return out
        x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        ws = jax.ShapeDtypeStruct((7, 128, 128), jnp.float32)
        txt = jax.jit(scanned).lower(ws, x).compile().as_text()
        c = hlo_cost.analyze(txt)
        assert c.flops == pytest.approx(7 * 2 * 128 ** 3, rel=0.01)

    def test_nested_scan(self):
        def nested(ws, x):
            def outer(c, w):
                def inner(c2, _):
                    return w @ c2, None
                c2, _ = jax.lax.scan(inner, c, jnp.arange(3))
                return c2, None
            out, _ = jax.lax.scan(outer, x, ws)
            return out
        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
        txt = jax.jit(nested).lower(ws, x).compile().as_text()
        c = hlo_cost.analyze(txt)
        assert c.flops == pytest.approx(15 * 2 * 64 ** 3, rel=0.01)

    def test_bytes_positive_and_bounded(self):
        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        txt = jax.jit(lambda a: a + 1.0).lower(x).compile().as_text()
        c = hlo_cost.analyze(txt)
        assert 0 < c.bytes <= 20 * 64 * 64 * 4


class TestRoofline:
    def test_terms_and_dominant(self):
        t = roofline.roofline_terms(197e12, 0.0, {"all-reduce": 50e9}, 4)
        assert t["t_compute_s"] == pytest.approx(1.0)
        assert t["t_collective_s"] == pytest.approx(1.0)
        assert t["dominant"] in ("compute", "collective")
        t2 = roofline.roofline_terms(0.0, 819e9, {}, 4)
        assert t2["t_memory_s"] == pytest.approx(1.0)
        assert t2["dominant"] == "memory"

    def test_model_flops_train_vs_decode(self):
        from repro.configs import get_config, INPUT_SHAPES
        cfg = get_config("llama3-8b")
        tr = roofline.model_flops(cfg, INPUT_SHAPES["train_4k"])
        de = roofline.model_flops(cfg, INPUT_SHAPES["decode_32k"])
        # train: 6·N·(256·4096 tokens); decode: 2·N·(128 tokens)
        assert tr / de == pytest.approx(
            (6 * 256 * 4096) / (2 * 128), rel=1e-6)


class TestShardingRules:
    def test_param_specs_cover_all_leaves(self):
        """Every leaf of every arch gets a valid spec (divisibility is the
        dry-run's job; here: no exceptions, correct rank)."""
        from repro.configs import ARCH_IDS, get_config
        from repro.configs.base import reduced
        from repro.launch import sharding
        from repro.launch.mesh import make_mesh
        from repro.models import build_model
        mesh = make_mesh((1, 1), ("data", "model"))
        for arch in ARCH_IDS:
            cfg = reduced(get_config(arch))
            model = build_model(cfg)
            shapes = jax.eval_shape(model.init, jax.random.key(0))
            sh = sharding.param_shardings(shapes, mesh)
            for leaf, s in zip(jax.tree.leaves(shapes), jax.tree.leaves(sh)):
                assert len(s.spec) <= leaf.ndim, (leaf.shape, s.spec)

    def test_layer_pspec_drops_stack_axis(self):
        from repro.launch import sharding
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 1), ("data", "model"))
        fn = sharding.layer_pspec_fn(mesh)
        spec = fn("wq", (64, 256))       # per-layer (D, H·hd)
        assert tuple(spec) == ("data", "model")


class TestInputSpecs:
    def test_all_arch_shape_combos_build(self):
        from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
        from repro.launch import specs
        from repro.models import build_model
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            for shape in INPUT_SHAPES.values():
                b = specs.input_specs(cfg, shape)
                assert "tokens" in b
                if shape.kind == "decode":
                    assert b["tokens"].shape == (shape.global_batch, 1)
                elif cfg.family == "vlm":
                    assert b["tokens"].shape[1] + cfg.num_image_tokens \
                        == shape.seq_len
                else:
                    assert b["tokens"].shape == (shape.global_batch,
                                                 shape.seq_len)

    def test_decode_specs_no_allocation(self):
        from repro.configs import get_config, INPUT_SHAPES
        from repro.launch import specs
        from repro.models import build_model
        cfg = get_config("rwkv6-7b")
        model = build_model(cfg)
        st = specs.decode_specs(model, INPUT_SHAPES["decode_32k"])
        for leaf in jax.tree.leaves(st):
            assert isinstance(leaf, jax.ShapeDtypeStruct) or leaf.size >= 0


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        from repro.ckpt import io
        params = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                  "nested": {"b": jnp.ones((4,), jnp.bfloat16)}}
        io.save(tmp_path / "step_10", params, step=10)
        restored, meta = io.restore(tmp_path / "step_10")
        assert meta["step"] == 10
        np.testing.assert_array_equal(np.asarray(params["a"]),
                                      np.asarray(restored["a"]))
        assert restored["nested"]["b"].dtype == jnp.bfloat16

    def test_latest_selection(self, tmp_path):
        from repro.ckpt import io
        for s in (1, 5, 3):
            io.save(tmp_path / f"step_{s}", {"w": jnp.zeros(2)}, step=s)
        path = io.latest(tmp_path)
        assert path.name == "step_5"


class TestData:
    def test_synthetic_dataset_shapes(self, dataset):
        assert dataset.x_train.shape[1] == 784
        assert dataset.y_train.shape[1] == 10
        assert dataset.x_train.min() >= 0.0
        assert dataset.x_train.max() <= 1.0
        # MNIST-like sparsity (stability regime for the paper's tau=0.1)
        assert (dataset.x_train == 0).mean() > 0.5

    def test_token_dataset(self):
        from repro.data import synthetic
        toks = synthetic.token_dataset(8, 32, 1000, seed=0)
        assert toks.shape == (8, 32)
        assert toks.min() >= 0 and toks.max() < 1000
