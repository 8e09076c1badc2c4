"""Multi-device behaviour via subprocesses (the main process must keep its
single CPU device — XLA locks device count at first init).

Covers: sharded training on a (2,2) mesh, elastic shrink after a simulated
node failure (restore-with-reshard + deterministic data replay), and
production-mesh construction with 512 placeholder devices.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_py(code: str, devices: int, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


@pytest.mark.slow
def test_sharded_training_matches_single_device():
    """Loss trajectory on a (2,2) mesh == single-device trajectory."""
    code = """
    import jax, numpy as np
    from repro.configs import get_config, reduced_config
    from repro.models import build_model
    from repro.train.trainer import TrainerConfig, init_train_state, make_train_step
    from repro.data.pipeline import SyntheticLMData, shard_batch
    from repro.launch.mesh import make_mesh
    from repro.parallel.sharding import RULES_TRAIN, set_activation_sharder
    from repro.optim.adamw import OptState
    from repro.train.trainer import TrainState
    from jax.sharding import NamedSharding, PartitionSpec as P

    assert len(jax.devices()) == 4
    cfg = reduced_config(get_config("llama32_1b"))
    model = build_model(cfg)
    tcfg = TrainerConfig(peak_lr=1e-3, warmup_steps=2, total_steps=10)
    data = SyntheticLMData(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)

    def run(mesh_shape):
        mesh = make_mesh(mesh_shape, ("data", "model"))
        axes, shapes = model.logical_axes(), model.init_shapes()
        p_sh = {k: RULES_TRAIN.sharding_for(axes[k], shapes[k].shape, mesh)
                for k in shapes}
        state_sh = TrainState(params=p_sh,
                              opt=OptState(mu=dict(p_sh), nu=dict(p_sh),
                                           count=NamedSharding(mesh, P())),
                              step=NamedSharding(mesh, P()))
        state = jax.device_put(
            __import__("repro.train.trainer", fromlist=["init_train_state"])
            .init_train_state(model, jax.random.PRNGKey(0), tcfg), state_sh)
        step = jax.jit(make_train_step(model, tcfg),
                       in_shardings=(state_sh, None), out_shardings=(state_sh, None))
        losses = []
        for i in range(6):
            with set_activation_sharder(mesh, RULES_TRAIN), mesh:
                db = shard_batch(data.batch_at(i), mesh, RULES_TRAIN)
                state, m = step(state, db)
            losses.append(float(m["loss"]))
        return losses

    l_multi = run((2, 2))
    l_single = run((1, 1))
    np.testing.assert_allclose(l_multi, l_single, rtol=2e-2, atol=2e-3)
    print("MULTIDEV_OK", l_multi[-1])
    """
    r = run_py(code, devices=4)
    assert "MULTIDEV_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_elastic_shrink_and_recover():
    """Simulated node failure at step 7: shrink data axis 4 -> 2, restore the
    latest checkpoint onto the new mesh, and keep training."""
    code = """
    import jax, numpy as np, tempfile
    from repro.configs import get_config, reduced_config
    from repro.models import build_model
    from repro.train.trainer import TrainerConfig
    from repro.train.elastic import ElasticConfig, ElasticTrainer
    from repro.data.pipeline import SyntheticLMData

    assert len(jax.devices()) == 4
    cfg = reduced_config(get_config("llama32_1b"))
    model = build_model(cfg)
    tcfg = TrainerConfig(peak_lr=1e-3, warmup_steps=2, total_steps=20)
    data = SyntheticLMData(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)
    with tempfile.TemporaryDirectory() as d:
        et = ElasticTrainer(model, tcfg,
                            ElasticConfig(data_shards=4, model_shards=1,
                                          checkpoint_every=5, checkpoint_dir=d),
                            data, failure_schedule={7: 2})
        state, history = et.run(12)
    assert len(et.events) == 2, et.events
    assert any("reconfigure to 2" in e for e in et.events)
    assert int(state.step) == 12
    losses = [h["loss"] for h in history]
    assert all(np.isfinite(losses))
    print("ELASTIC_OK", et.events, losses[-1])
    """
    r = run_py(code, devices=4)
    assert "ELASTIC_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_production_mesh_512():
    """make_production_mesh builds both the 16x16 and 2x16x16 meshes with 512
    placeholder devices, and a tiny step lowers+compiles on each."""
    code = """
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_production_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    single = make_production_mesh()
    multi = make_production_mesh(multi_pod=True)
    assert dict(single.shape) == {"data": 16, "model": 16}
    assert dict(multi.shape) == {"pod": 2, "data": 16, "model": 16}

    x = jax.ShapeDtypeStruct((512, 128), jnp.float32)
    for mesh, spec in ((single, P("data", "model")),
                       (multi, P(("pod", "data"), "model"))):
        sh = NamedSharding(mesh, spec)
        f = jax.jit(lambda a: (a * 2).sum(), in_shardings=(sh,))
        compiled = f.lower(x).compile()
        assert compiled.cost_analysis() is not None
    print("MESH512_OK")
    """
    r = run_py(code, devices=512)
    assert "MESH512_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_ep_moe_matches_dense():
    """shard_map expert-parallel MoE (the §Perf dispatch fix) computes the
    same function as the dense reference, for both the expert-sharded (E
    divides model axis) and FFN-sharded (E doesn't divide) paths."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import MoEConfig, ModelConfig
    from repro.models import layers as L
    from repro.parallel.sharding import RULES_TRAIN, set_activation_sharder

    for E in (8, 6):
        cfg = ModelConfig(name="t", family="moe", num_layers=2, d_model=32,
                          num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                          moe=MoEConfig(num_experts=E, top_k=2, d_ff_expert=64))
        rng = np.random.default_rng(0)
        p = {"moe/router": jnp.asarray(rng.standard_normal((32, E)) * 0.1, jnp.float32),
             "moe/we_gate": jnp.asarray(rng.standard_normal((E, 32, 64)) * 0.1, jnp.float32),
             "moe/we_up": jnp.asarray(rng.standard_normal((E, 32, 64)) * 0.1, jnp.float32),
             "moe/we_down": jnp.asarray(rng.standard_normal((E, 64, 32)) * 0.1, jnp.float32)}
        x = jnp.asarray(rng.standard_normal((4, 16, 32)), jnp.float32)
        y_ref, aux_ref = L.moe_apply_dense(cfg, p, "moe", x)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        with set_activation_sharder(mesh, RULES_TRAIN), mesh:
            y, aux = jax.jit(lambda p, x: L.moe_apply_dropless_ep(
                cfg, p, "moe", x, capacity_factor=4.0))(p, x)
            g = jax.jit(jax.grad(lambda p, x: L.moe_apply_dropless_ep(
                cfg, p, "moe", x, capacity_factor=4.0)[0].sum()))(p, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-4)
        assert all(np.all(np.isfinite(np.asarray(v))) for v in g.values())
    print("EPMOE_OK")
    """
    r = run_py(code, devices=8)
    assert "EPMOE_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_compressed_train_step_tracks_exact():
    """End-to-end: the int8-EF compressed cross-pod train step follows the
    exact train step's loss trajectory on a (pod=2, data=2, model=2) mesh."""
    code = """
    import jax, jax.numpy as jnp, dataclasses
    from repro.configs import get_config, reduced_config
    from repro.models import build_model
    from repro.train.trainer import (TrainerConfig, init_train_state,
                                     make_train_step, make_train_step_compressed,
                                     init_compression_errors)
    from repro.data.pipeline import SyntheticLMData

    cfg = dataclasses.replace(reduced_config(get_config("llama32_1b")),
                              dtype="float32")
    model = build_model(cfg)
    tcfg = TrainerConfig(peak_lr=1e-3, warmup_steps=2, total_steps=20,
                         compute_dtype="float32")
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    data = SyntheticLMData(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)
    state_c = init_train_state(model, jax.random.PRNGKey(0), tcfg)
    state_r = jax.tree.map(jnp.copy, state_c)
    err = init_compression_errors(model, mesh, 2)
    with mesh:
        step_c = jax.jit(make_train_step_compressed(model, tcfg, mesh, None, None))
        step_r = jax.jit(make_train_step(model, tcfg))
        for i in range(6):
            batch = data.batch_at(i)
            state_c, err, mc = step_c(state_c, err, batch)
            state_r, mr = step_r(state_r, batch)
    diff = abs(float(mc["loss"]) - float(mr["loss"]))
    assert diff < 0.05, diff
    print("COMPTRAIN_OK", diff)
    """
    r = run_py(code, devices=8)
    assert "COMPTRAIN_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_compressed_cross_pod_psum():
    """int8 error-feedback gradient all-reduce over a 2-pod axis inside
    shard_map matches the exact mean within quantization tolerance."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.optim.grad_compress import (compressed_cross_pod_mean,
                                           init_compression_state)

    mesh = jax.make_mesh((2,), ("pod",))
    g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal((2, 64)),
                          jnp.float32)}
    state = init_compression_state({"w": g["w"][0]})

    def f(gl, err):
        out, new_state = compressed_cross_pod_mean(
            {"w": gl["w"][0]}, state._replace(error={"w": err["w"][0]}), "pod")
        return out["w"], new_state.error["w"]

    sm = jax.shard_map(f, mesh=mesh,
                       in_specs=({"w": P("pod")}, {"w": P("pod")}),
                       out_specs=(P(), P("pod")))
    err0 = {"w": jnp.zeros((2, 64), jnp.float32)}
    mean, new_err = sm(g, err0)
    want = np.asarray(g["w"]).mean(0)
    got = np.asarray(mean)
    rel = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert rel < 0.05, rel
    print("COMPRESS_OK", rel)
    """
    r = run_py(code, devices=2)
    assert "COMPRESS_OK" in r.stdout, r.stdout + r.stderr
