"""Optimizer + gradient compression."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.optim import (AdamWConfig, adamw_update, compress, decompress,
                         global_norm, init_adamw, init_ef, lr_schedule,
                         make_compressed_psum)


def test_adamw_converges_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, grad_clip=0.0,
                      warmup_steps=0, total_steps=200, min_lr_frac=1.0)
    target = jnp.array([1.5, -2.0, 0.5])
    params = {"w": jnp.zeros(3)}
    opt = init_adamw(params, cfg)
    for _ in range(150):
        g = {"w": 2 * (params["w"] - target)}
        params, opt, _ = adamw_update(params, g, opt, cfg)
    assert float(jnp.abs(params["w"] - target).max()) < 0.05


def test_lr_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_frac=0.1)
    lrs = [float(lr_schedule(cfg, jnp.int32(s))) for s in range(101)]
    assert lrs[0] < 0.15 and abs(lrs[10] - 1.0) < 1e-5
    assert abs(lrs[100] - 0.1) < 1e-5
    assert all(a >= b - 1e-9 for a, b in zip(lrs[10:], lrs[11:]))


def test_grad_clip():
    cfg = AdamWConfig(lr=0.0, grad_clip=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    opt = init_adamw(params, cfg)
    big = {"w": jnp.full(4, 100.0)}
    _, _, m = adamw_update(params, big, opt, cfg)
    assert float(m["grad_norm"]) == pytest.approx(200.0)


def test_decay_mask_skips_norms():
    cfg = AdamWConfig(lr=0.1, weight_decay=1.0, grad_clip=0.0,
                      warmup_steps=0, min_lr_frac=1.0)
    params = {"dense": {"w": jnp.ones(2)}, "norm1": {"scale": jnp.ones(2)}}
    opt = init_adamw(params, cfg)
    zero_g = jax.tree.map(jnp.zeros_like, params)
    p2, _, _ = adamw_update(params, zero_g, opt, cfg)
    assert float(p2["dense"]["w"][0]) < 1.0          # decayed
    assert float(p2["norm1"]["scale"][0]) == 1.0     # masked


def test_compression_error_feedback_bounded(rng):
    g = {"w": jnp.array(rng.normal(size=256).astype(np.float32))}
    ef = init_ef(g)
    acc_true = np.zeros(256)
    acc_q = np.zeros(256)
    for step in range(50):
        gi = {"w": jnp.array(rng.normal(size=256).astype(np.float32))}
        q, s, ef = compress(gi, ef)
        dq = decompress(q, s)
        acc_true += np.asarray(gi["w"])
        acc_q += np.asarray(dq["w"])
    # with EF the *accumulated* quantized signal tracks the true sum
    err = np.abs(acc_q + np.asarray(ef.err["w"]) - acc_true).max()
    assert err < 1e-3


def test_compressed_psum_multidev():
    import os, subprocess, sys, textwrap
    script = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.optim import init_ef, make_compressed_psum
        mesh = jax.make_mesh((4,), ('dp',))
        g = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 7.0
        ef = init_ef({'w': g[0]})
        cpsum = make_compressed_psum('dp')
        def f(gs, err):
            red, ef2 = cpsum({'w': gs}, type(ef)(err={'w': err}))
            return red['w'], ef2.err['w']
        out, _ = jax.shard_map(f, mesh=mesh, in_specs=(P('dp'), P('dp')),
                               out_specs=(P('dp'), P('dp')))(
            g, jnp.zeros_like(g))
        want = np.asarray(g).sum(0)
        got = np.asarray(out)[0]
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        assert rel < 0.02, (got, want)
        print('CPSUM_OK')
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "CPSUM_OK" in r.stdout, r.stdout + r.stderr
