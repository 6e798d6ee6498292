"""Distributed-path tests: a subprocess with 8 virtual host devices runs a
sharded train step + sharded decode and checks numerics against the
single-device result, or runs serving replicas on separate devices. (A
subprocess is required because jax locks the device count at first init;
see launch/dryrun.py.)"""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    stdout = _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import smoke_config
        from repro.launch import sharding as shd
        from repro.models import Model
        from repro.training import optimizer
        from repro.training.train_loop import make_train_step

        cfg = smoke_config("h2o-danube-1.8b")
        mesh = make_mesh((2, 4), ("data", "model"))
        model = Model(cfg, remat=False)
        params = model.init(jax.random.PRNGKey(0))
        opt = optimizer.init(params)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                              (4, 64), 0, cfg.vocab_size)}
        batch["labels"] = batch["tokens"]

        # single-device reference
        step0 = jax.jit(make_train_step(model, optimizer.OptConfig()))
        _, _, m0 = step0(params, opt, batch)

        policy = shd.MeshPolicy(mesh, cfg)
        p_shape = jax.eval_shape(lambda: params)
        p_shard = shd.param_shardings(p_shape, mesh, cfg)
        o_shard = shd.param_shardings(jax.eval_shape(lambda: opt), mesh, cfg)
        b_shard = shd.batch_shardings(
            jax.eval_shape(lambda: batch), mesh, cfg)
        params_s = jax.device_put(params, p_shard)
        opt_s = jax.device_put(opt, o_shard)
        batch_s = jax.device_put(batch, b_shard)
        step1 = jax.jit(make_train_step(model, optimizer.OptConfig(),
                                        policy),
                        in_shardings=(p_shard, o_shard, b_shard))
        _, _, m1 = step1(params_s, opt_s, batch_s)
        print("loss0", float(m0["loss"]), "loss1", float(m1["loss"]))
        assert abs(float(m0["loss"]) - float(m1["loss"])) < 0.03, \\
            (float(m0["loss"]), float(m1["loss"]))
        print("SHARDED_OK")
        """)
    assert "SHARDED_OK" in stdout


@pytest.mark.slow
def test_sharded_moe_matches_single_device():
    # Tolerances, measured and justified (this test used to assert bf16
    # max-logit-err < 0.08 and failed at 0.0898 — a marginal, ill-posed
    # bound):
    #
    # * float32 run, max err < 5e-3 (measured 1.0e-3; the residual is
    #   generic sharded-compilation reduction reordering, not the MoE
    #   mapping — an expert-routing or psum bug would be O(0.1+)). This is
    #   the correctness check for the expert-parallel shard_map path.
    # * bf16 run, MEAN err < 0.01 and argmax agreement >= 0.97: bf16
    #   hidden-state noise can flip a borderline router top-k choice for
    #   isolated tokens, and a flipped expert changes those logits by
    #   O(0.1) — so the bf16 MAX err is not boundable tightly; the bulk
    #   statistics are.
    # The reference is jitted like the sharded run, so the two differ only
    # by the sharding: an op-by-op reference rounds bf16 differently from
    # any compiled program (it agrees with the unsharded jit on only 124 of
    # 128 argmaxes, all four at top-2 gaps of one bf16 ulp or less).
    stdout = _run("""
        import dataclasses
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import smoke_config
        from repro.launch import sharding as shd
        from repro.models import Model

        def compare(dtype):
            cfg = smoke_config("deepseek-v2-236b")  # MLA + MoE(4 experts)
            cfg = dataclasses.replace(cfg, dtype=dtype)
            mesh = make_mesh((2, 4), ("data", "model"))
            model = Model(cfg, remat=False)
            params = model.init(jax.random.PRNGKey(0))
            tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                        cfg.vocab_size)
            logits0 = jax.jit(lambda p, t: model.forward(p, t)[0])(params,
                                                                   tokens)
            policy = shd.MeshPolicy(mesh, cfg)
            p_shard = shd.param_shardings(jax.eval_shape(lambda: params),
                                          mesh, cfg)
            params_s = jax.device_put(params, p_shard)
            fwd = jax.jit(lambda p, t: model.forward(p, t,
                                                     policy=policy)[0],
                          in_shardings=(p_shard, None))
            logits1 = fwd(params_s, tokens)
            d = jnp.abs(logits0.astype(jnp.float32)
                        - logits1.astype(jnp.float32))
            agree = jnp.mean((jnp.argmax(logits0, -1)
                              == jnp.argmax(logits1, -1)).astype(
                                  jnp.float32))
            return float(jnp.max(d)), float(jnp.mean(d)), float(agree)

        mx32, mean32, _ = compare("float32")
        print("f32 max err", mx32, "mean", mean32)
        assert mx32 < 5e-3, mx32
        mx16, mean16, agree16 = compare("bfloat16")
        print("bf16 max err", mx16, "mean", mean16, "agree", agree16)
        assert mean16 < 0.01, mean16
        assert agree16 >= 0.97, agree16
        print("MOE_SHARDED_OK")
        """)
    assert "MOE_SHARDED_OK" in stdout


@pytest.mark.slow
def test_dist_attention_on_mesh():
    stdout = _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core.distkv import dist_attention, dist_attention_ref
        mesh = make_mesh((2, 4), ("data", "model"))
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (4, 8, 64))
        k = jax.random.normal(ks[1], (4, 256, 2, 64))
        v = jax.random.normal(ks[2], (4, 256, 2, 64))
        lens = jnp.array([3, 100, 256, 177], jnp.int32)
        out = dist_attention(mesh, q, k, v, lens)
        want = dist_attention_ref(q, k, v, lens)
        err = float(jnp.max(jnp.abs(out - want)))
        assert err < 1e-5, err
        print("DIST_ATTN_OK")
        """)
    assert "DIST_ATTN_OK" in stdout


@pytest.mark.parametrize("share_mode", ["copy", "zero_copy"])
def test_replicas_share_prefix_across_devices(share_mode):
    """Two engine replicas on two devices (built like the launcher builds
    them): each keeps its KV pools on its own device, and a prefix
    published by one is adopted (copy) or leased (zero_copy) by the other
    across devices, decoding the tokens the model's own decode gives."""
    stdout = _run(f"""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import smoke_config
        from repro.core.scheduling import Request
        from repro.launch.serve import build_engine
        from repro.models import Model
        from repro.serving.engine import EngineConfig
        from repro.serving.router import RouterBackend

        cfg = dataclasses.replace(smoke_config("h2o-danube-1.8b"),
                                  sliding_window=None, logits_fp32=True)
        devs = jax.devices()[:2]
        engines = [build_engine(cfg, EngineConfig(
            num_pages=64, page_size=8, max_slots=4,
            enable_prefix_cache=True), device=d) for d in devs]
        for e, d in zip(engines, devs):
            assert all(p.devices() == {{d}} for p in e.pools)

        class Scripted:
            def __init__(self):
                self.order = iter([0, 0, 1])
            def choose(self, req, children):
                return next(self.order)

        router = RouterBackend(engines, policy=Scripted(), prefix_share=True,
                               share_mode="{share_mode}", hot_threshold=1)
        rng = np.random.default_rng(12)
        prefix = rng.integers(0, cfg.vocab_size, 16).tolist()
        reqs = [Request(i, 0.0, prefix +
                        rng.integers(0, cfg.vocab_size, 4).tolist(),
                        max_new_tokens=3) for i in range(3)]
        for r in reqs:
            router.add_request(r)
            while router.has_work:
                router.step()
        assert reqs[2].instance_id == 1
        assert reqs[2].num_cached_tokens == 16
        moved = engines[1].prefix_cache.adopted_pages \\
            if "{share_mode}" == "copy" else router.pages_borrowed
        assert moved == 2, moved

        model = Model(cfg, remat=False)
        for r in reqs:
            logits, caches = model.prefill(
                engines[0].params, jnp.asarray(r.prompt, jnp.int32)[None],
                seq_capacity=64)
            want = [int(jnp.argmax(logits[0]))]
            while len(want) < 3:
                logits, caches = model.decode_step(
                    engines[0].params, jnp.array([[want[-1]]], jnp.int32),
                    jnp.array([r.prompt_len + len(want) - 1], jnp.int32),
                    caches)
                want.append(int(jnp.argmax(logits[0])))
            assert r.full_output == want, (r.request_id, r.full_output, want)
        print("REPLICAS_OK")
        """)
    assert "REPLICAS_OK" in stdout
