"""Distributed behaviour on a small multi-device CPU mesh.

Each test runs in a subprocess so the 8-device
``xla_force_host_platform_device_count`` override never leaks into the
rest of the suite (per the dry-run contract: only launch/dryrun.py and
explicit subprocesses may change the device count)."""

import json
import os
import subprocess
import sys
import textwrap


SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_in_mesh_subprocess(body: str, devices: int = 8, timeout: int = 600):
    """Run `body` with N host devices; returns parsed RESULT json line."""
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={devices}")
        import json
        import numpy as np
        import jax
        import jax.numpy as jnp
        assert jax.device_count() == {devices}
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"STDOUT:{out.stdout}\nSTDERR:{out.stderr}"
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[7:])
    return None


def test_sharded_train_step_matches_single_device():
    """Same config/batch: (2×4)-mesh sharded training == 1-device numerics."""
    r = run_in_mesh_subprocess("""
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.train import init_train_state, make_train_step
        from repro.distributed.sharding import make_rules
        from repro.launch.mesh import make_smoke_mesh

        cfg = get_smoke_config("olmo-1b")
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                                    (4, 33)))}

        # single-device reference
        m1 = build_model(cfg)
        s1 = init_train_state(m1, jax.random.key(0))
        f1 = jax.jit(make_train_step(m1, peak_lr=1e-3))
        s1, met1 = f1(s1, batch)

        # sharded
        mesh = make_smoke_mesh(2, 4)
        rules = make_rules(mesh)
        m2 = build_model(cfg, rules)
        with mesh:
            s2 = init_train_state(m2, jax.random.key(0))
            f2 = jax.jit(make_train_step(m2, peak_lr=1e-3))
            s2, met2 = f2(s2, batch)

        d_loss = abs(float(met1["loss"]) - float(met2["loss"]))
        d_par = max(float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(s1.params),
                            jax.tree.leaves(s2.params)))
        print("RESULT", json.dumps({"d_loss": d_loss, "d_par": d_par}))
    """)
    assert r["d_loss"] < 1e-4, r
    assert r["d_par"] < 5e-3, r


def test_moe_sharded_matches_local():
    """shard_map EP == single-device MoE (no-drop capacity)."""
    r = run_in_mesh_subprocess("""
        from dataclasses import replace
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.distributed.sharding import make_rules
        from repro.launch.mesh import make_smoke_mesh

        cfg = get_smoke_config("qwen3-moe-235b-a22b")
        cfg = cfg.with_(moe=replace(cfg.moe, capacity_factor=16.0))
        rng = np.random.default_rng(1)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)))

        m1 = build_model(cfg)
        p = m1.init(jax.random.key(0))
        ref, aux1 = m1.forward(p, tokens)

        mesh = make_smoke_mesh(2, 4)
        rules = make_rules(mesh)
        m2 = build_model(cfg, rules)
        with mesh:
            got, aux2 = jax.jit(m2.forward)(p, tokens)
        err = float(jnp.max(jnp.abs(got - ref)))
        print("RESULT", json.dumps({"err": err,
                                    "d_aux": abs(float(aux1-aux2))}))
    """)
    assert r["err"] < 5e-4, r
    # aux load-balance loss is E·Σ f_e·P_e — nonlinear in the batch split,
    # so per-dp-shard-then-pmean differs slightly from the global estimate
    assert r["d_aux"] < 5e-3, r


def test_compressed_psum_correct():
    r = run_in_mesh_subprocess("""
        from repro.distributed.compression import compressed_psum
        from jax.sharding import PartitionSpec as P
        mesh = jax.make_mesh((8,), ("d",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        x = jnp.asarray(np.random.default_rng(0)
                        .standard_normal((8, 64)), jnp.float32)

        def f(x):
            return compressed_psum(x, "d")

        y = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("d"),
                                  out_specs=P("d")))(x)
        # compressed mean-psum ≈ plain mean over the axis
        want = jnp.broadcast_to(x.reshape(8, 1, 64).mean(0), (8, 1, 64))
        want = want.reshape(8, 64)
        err = float(jnp.max(jnp.abs(y - want)))
        rel = err / float(jnp.max(jnp.abs(want)))
        print("RESULT", json.dumps({"rel": rel}))
    """)
    assert r["rel"] < 0.05, r     # int8 quantization error bound


def test_elastic_checkpoint_restore_across_meshes():
    """Save on a (2,4) mesh → restore on (4,2): values identical."""
    r = run_in_mesh_subprocess("""
        import tempfile
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.distributed.sharding import make_rules, param_pspecs
        from repro.checkpoint import save_checkpoint, restore_checkpoint
        from repro.launch.mesh import make_smoke_mesh

        cfg = get_smoke_config("stablelm-1.6b")
        mesh1 = make_smoke_mesh(2, 4)
        m = build_model(cfg, make_rules(mesh1))
        with mesh1:
            p = m.init(jax.random.key(0))
        d = tempfile.mkdtemp()
        save_checkpoint(d, 1, p)

        mesh2 = make_smoke_mesh(4, 2)
        rules2 = make_rules(mesh2)
        specs = param_pspecs(jax.eval_shape(lambda: p), rules2)
        shardings = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(mesh2, s), specs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        got, step, _ = restore_checkpoint(d, p, shardings=shardings)
        err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                        - b.astype(jnp.float32))))
                  for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(got)))
        ok_sharded = all(
            g.sharding.mesh.shape == {"data": 4, "model": 2}
            for g in jax.tree.leaves(got))
        print("RESULT", json.dumps({"err": err, "sharded": ok_sharded}))
    """)
    assert r["err"] == 0.0
    assert r["sharded"] is True
